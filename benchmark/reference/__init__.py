"""The plain reference: each served architecture's forward pass in
straightforward float32 ``jax.numpy`` — no kernels, no cache, no batching.
Independent of the program's model code; see ``forward.py``."""
