"""A new KIND of reducer, added by a file (the rehearsal copies it to
``<root>/benchmark/reducer_files/tiny_hybrid.py``): what a new kernel's
cost functions look like — bytes from the configuration's own widths
(``Measured.config``), a count from the window's counters."""
from benchmark.reducers import Measured, reducer


def expert_weight_bytes(config: dict, bytes_per_weight: int) -> int:
    """Weight bytes one token's experts read in one layer: three matrices
    of ``hidden x intermediate`` for each of its experts."""
    return (3 * config["hidden_size"] * config["intermediate_size"]
            * config["num_experts_per_tok"] * bytes_per_weight)


@reducer
def expert_bytes_per_token(m: Measured, a: dict) -> float | None:
    if "hidden_size" not in m.config:
        return None
    return float(expert_weight_bytes(m.config, a["bytes_per_weight"])
                 * m.config["num_hidden_layers"])
