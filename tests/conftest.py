"""Test configuration.

JAX runs on 8 virtual CPU devices (the standard trick for exercising
multi-chip mesh/collective code without TPU hardware — SURVEY.md §4c).
Every setting below must be in place before any jax import, hence here at
conftest import time, and in the ENVIRONMENT, so that a child process a
test starts compiles and imports as its worker does: ``JAX_PLATFORMS=cpu``
keeps every test on the host (fp32 numerics comparisons need the CPU's
matmul precision), and the XLA flag provides the devices.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# A test's program is a two-layer model a few dozen wide, run for a handful
# of steps: compiling it as if it would serve is most of a file's seconds.
# jax's own setting for that case (backend optimisation level 0, LLVM's
# expensive passes off). What asserts on a COMPILED program's text or cost
# restores full effort for its module: ``full_effort_uncached`` in
# tests/test_aot_tpu_compile.py.
os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"
# ``transformers`` loads TensorFlow and Flax where it finds them; the files
# that build a tiny HF checkpoint use torch alone.
os.environ["USE_TF"] = "0"
os.environ["USE_FLAX"] = "0"

# One XLA compilation cache a PROCESS, thrown away with it (every compile
# goes in: no floor on its seconds or its bytes). Engines and kernels of
# one configuration are built again and again by the cases of a file,
# each build traces anew, and an interpreted Pallas kernel compiles for
# seconds: with the cache the second build of a program is a read, which
# halves the engine files. Not one directory for the run: xdist's workers
# would write entries side by side (jax writes them in place, not by
# rename). The directory is set whatever the environment says, so no run
# reads what another left. The engine's cache PLACEMENT has its own tests
# (test_compilation_cache.py).
import atexit           # noqa: E402
import shutil           # noqa: E402
import tempfile         # noqa: E402

if os.environ.get("_TIER1_XLA_CACHE_OF") != str(os.getpid()):
    # (not again when a test imports this file as ``tests.conftest``)
    os.environ["_TIER1_XLA_CACHE_OF"] = str(os.getpid())
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="tier1-xla-cache-")
    atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                    ignore_errors=True)
_XLA_CACHE = os.environ["JAX_COMPILATION_CACHE_DIR"]
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "1"
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import jax  # noqa: E402


def cpu_devices():
    """The 8 virtual CPU devices for mesh tests."""
    return jax.devices("cpu")

import asyncio          # noqa: E402
import faulthandler     # noqa: E402
import inspect          # noqa: E402
import signal           # noqa: E402
import threading        # noqa: E402
from pathlib import Path  # noqa: E402

import pytest           # noqa: E402


_loop = None


def _shared_loop():
    """One persistent event loop for every async test — long-lived objects
    (the engine's batching loop, queues, events) stay bound to a live loop
    across tests, matching the single-loop production process."""
    global _loop
    if _loop is None or _loop.is_closed():
        _loop = asyncio.new_event_loop()
    return _loop


@pytest.fixture(scope="session", autouse=True)
def graft_sanitizer():
    """Runtime asyncio sanitizer (graftlint v2, ISSUE 5) armed for the
    ENTIRE tier-1 suite: every chaos/obs/engine test doubles as a race
    hunt. Three detectors (analysis/sanitizer.py): an event-loop stall
    detector (any callback step over the threshold, with a mid-stall
    stack sample), a guarded-field tracker enforcing the `# guarded-by:`
    annotations on live engine/router/config/db objects, and task/span
    leak checks at session teardown. Violations fail the session — the
    dynamic analog of test_graftlint's static live-tree gate.

    GRAFT_SANITIZER=0 disables; GRAFT_SANITIZER_STALL_S tunes the stall
    threshold (default 5 s: far above any legitimate await-to-await step,
    below a wedged loop; XLA compiles run in worker threads and never
    count, but first-call tracing inside an async test body can
    legitimately take seconds on a cold CPU cache)."""
    if os.environ.get("GRAFT_SANITIZER", "1") == "0":
        yield None
        return
    from llmapigateway_tpu.analysis.sanitizer import (
        AsyncioSanitizer, default_instrumented_classes)
    san = AsyncioSanitizer(stall_threshold_s=float(
        os.environ.get("GRAFT_SANITIZER_STALL_S", "5.0")))
    san.install()
    san.instrument_classes(default_instrumented_classes())
    yield san
    loop = _loop if _loop is not None and not _loop.is_closed() else None
    san.check_leaks(loop)
    report = san.report()
    san.uninstall()
    assert not san.violations(), report


@pytest.fixture(scope="session")
def stop_engine():
    """Fixture-teardown helper: stop an engine ON THE SHARED LOOP so its
    batching-loop task is awaited (not garbage-collected mid-flight —
    'Task was destroyed but it is pending'). A fixture, not an importable
    function: pytest loads conftest under its own module name, so a
    ``from tests.conftest import ...`` in a test would get a SECOND module
    instance with a second (wrong) loop."""
    def _stop(eng):
        _shared_loop().run_until_complete(eng.stop())
        assert_all_free(eng)
    return _stop


def assert_all_free(eng) -> None:
    """What only a page pool can state, after a case or a stop: no request
    holds a slot, and every cache group's allocator has all its pages back
    (free, or resident in the radix cache, which keeps finished prefixes)
    with its books in order. A page leaked on cancel, on ``max_tokens``, on
    a fault's rebuild or on a supervisor restart fails here by name."""
    assert not eng._running and not eng._prefilling, (
        eng._running, eng._prefilling)
    assert eng._free_slot_count() == eng.B, [p.free for p in eng._pools]
    for g in eng.kv_groups:
        a = g.allocator
        cache = eng._prefix_cache if a is eng.allocator else None
        kept = cache.resident_pages if cache is not None else 0
        assert a.free_pages + kept == a.num_pages - a.pages_per_block, (
            g.stats(), a.free_pages, kept)
        # The radix cache checks its allocator with its pins counted.
        (cache or a).check_invariants()


@pytest.fixture(scope="session")
def all_free():
    """:func:`assert_all_free`, for a case that checks in mid-flight (a
    fixture for the reason ``stop_engine`` is one)."""
    return assert_all_free


@pytest.fixture
def engine(shared_engine):
    """A file's module-scoped ``shared_engine``, held after every case that
    used it to :func:`assert_all_free` (a file with an ``engine`` fixture
    of its own overrides this one)."""
    yield shared_engine
    assert_all_free(shared_engine)


@pytest.fixture
def build_engine(stop_engine):
    """``build_engine(cfg, **kw) -> InferenceEngine``; whatever a case
    built is stopped on the shared loop when the case ends, and must then
    have given every page and slot back."""
    from llmapigateway_tpu.engine.engine import InferenceEngine
    built = []

    def build(cfg, **kw):
        built.append(InferenceEngine(cfg, **kw))
        return built[-1]
    yield build
    for eng in built:
        stop_engine(eng)


# ``--dist loadfile`` hands a worker whole files in collection order, two
# at a time, so a long file late in the alphabet starts in the run's last
# minutes and is the wall's tail with five workers idle. The files that
# hold a worker longest go out first, longest first (seconds on a worker,
# the sum of a file's cases in a whole run's junit XML; PERF.md section 6
# has the run); the rest keep their order, a file its cases' order. A
# stale list costs balance, nothing else.
LONGEST_FIRST = (
    "test_spec_discovery.py",               # 373 (tests/bench_harness/)
    "test_kv_quant.py",                     # 265
    "test_model_hybrid.py",                 # 224
    "test_aot_tpu_state_families.py",       # 221
    "test_keye_vl2_rehearsal.py",           # 182 (tests/bench_harness/)
    "test_ops_paged_decode_fold.py",        # 178
    "test_solar_open2_rehearsal.py",        # 178 (tests/bench_harness/)
    "test_speculative.py",                  # 174
    "test_ops_grouped_experts.py",          # 172
    "test_engine_pool_in_place.py",         # 163
    "test_engine_hybrid.py",                # 158
    "test_ops_paged_prefill_fold.py",       # 157
    "test_smallthinker_rehearsal.py",       # 150 (tests/bench_harness/)
    "test_phi4_flash_rehearsal.py",         # 145 (tests/bench_harness/)
    "test_aot_tpu_programs.py",             # 144
    "test_model_cohere2.py",                # 139
    "test_model_phi4_flash.py",             # 135
    "test_quant.py",                        # 134
    "test_ops_paged_chunk_write.py",        # 133
    "test_model_keye_vl2.py",               # 132
    "test_queued_metric_files.py",          # 131 (tests/bench_harness/)
    "test_engine.py",                       # 128
    "test_model_smallthinker.py",           # 127
    "test_command_a_plus_rehearsal.py",     # 127 (tests/bench_harness/)
    "test_engine_cache_groups.py",          # 122
    "test_aot_tpu_phi4_flash.py",           # 122
    "test_engine_paged.py",                 # 121
    "test_chip_smoke.py",                   # 118
    "test_ops_grouped_experts_edges.py",    # 117
    "test_ops_paged_in_place.py",           # 115
    "test_model_hybrid_experts.py",         # 115
    "test_gigachat35_rehearsal.py",         # 114 (tests/bench_harness/)
    "test_model_gigachat35.py",             # 114
    "test_model_mistral4.py",               # 113
    "test_engine_pool_carried.py",          # 109
    "test_ops_paged.py",                    # 100
    "test_prefill_pool_carried.py",         # 99
    "test_aot_tpu_compile.py",              # 97
    "test_request_wait_metric_files.py",    # 89 (tests/bench_harness/)
    "test_ops_paged_multipage.py",          # 88
    "test_model_mistral.py",                # 88
    "test_mistral_small4_rehearsal.py",     # 81 (tests/bench_harness/)
    "test_hybrid_programs_pinned.py",       # 81
    "test_engine_supervision.py",           # 75
)


# ONE case under tests/bench_harness/ (the benchmark's own files: no later
# PR may edit them) that pins the END of BENCHMARK.json's lists as PR 51 left
# it. Every later cell stands behind that end, so the case can only fail; it
# is kept, expected to, until a ``benchmark`` PR pins it from the front (as
# the cells of PR 46 and PR 54 are pinned) and DELETES this table with it
# (PERF.md section 7). The marker silences the whole case, what still holds
# in it too, so no later PR adds an entry here: a new rehearsal pins from
# the front.
SUPERSEDED = {
    "tests/bench_harness/test_keye_vl2_rehearsal.py::"
    "test_the_new_entries_stand_at_the_end_and_the_cell_joined_its_lists":
        "pins PR 51's entries as the LAST of BENCHMARK.json; PR 54 appended "
        "a configuration, a cell and eight metrics behind them",
}


def pytest_collection_modifyitems(items):
    rank = {name: at for at, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))
    for item in items:
        why = SUPERSEDED.get(item.nodeid)
        if why:
            item.add_marker(pytest.mark.xfail(reason=why, strict=True))


def pytest_unconfigure(config):
    """An xdist worker may leave without running ``atexit``."""
    shutil.rmtree(_XLA_CACHE, ignore_errors=True)


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on the shared loop (no pytest-asyncio here)."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        _shared_loop().run_until_complete(func(**kwargs))
        return True
    return None


# The driver cuts the whole run at 1,470 s and counts only what ran before
# the cut, so no one wait may be longer than the suite. A test that runs
# past PER_TEST_LIMIT_S fails with its name and every thread's stack. The
# slowest case the file serves is a 213 s rehearsal under
# tests/bench_harness/ (six files side by side on eight cores); the static
# case in tests/test_time_limit.py holds every subprocess and wait_for
# timeout under tests/ to this limit.
PER_TEST_LIMIT_S = 500


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item, limit_s=PER_TEST_LIMIT_S):
    """Fail a test that runs past ``limit_s``. SIGALRM interrupts the
    worker's main thread (xdist runs tests there; on any other thread a
    handler cannot be set and the test runs unbounded, as before) and
    keeps firing every 5 s until the failure is out — an event-loop
    callback or an ``except BaseException`` can swallow one raise.
    ``faulthandler`` prints the stacks from its own thread, so they
    appear even while the main thread is inside a call that never
    returns to the interpreter."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)

    def past_the_limit(signum, frame):
        pytest.fail(f"{item.nodeid} ran past the per-test limit of "
                    f"{limit_s} s (tests/conftest.py)")
    before = signal.signal(signal.SIGALRM, past_the_limit)
    faulthandler.dump_traceback_later(limit_s)
    signal.setitimer(signal.ITIMER_REAL, limit_s, 5.0)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, before)


PROVIDERS_JSON5 = """\
[
    // comments must survive round-trips
    { "fakeup": { "baseUrl": "http://127.0.0.1:1/v1", "apikey": "FAKE_KEY_ENV" } },
    { "openrouter": { "baseUrl": "http://127.0.0.1:1/v1", "apikey": "sk-or-literal" } },
]
"""

RULES_JSON5 = """\
[
    {
        "gateway_model_name": "gw/test-model",
        "rotate_models": "false",
        "fallback_models": [
            { "provider": "fakeup", "model": "real-model-a", "retry_count": 1, "retry_delay": 0.01 },
            { "provider": "openrouter", "model": "real-model-b" },
        ],
    },
    {
        "gateway_model_name": "gw/rotating",
        "rotate_models": true,
        "fallback_models": [
            { "provider": "fakeup", "model": "rot-a" },
            { "provider": "fakeup", "model": "rot-b" },
            { "provider": "fakeup", "model": "rot-c" },
        ],
    },
]
"""


@pytest.fixture
def config_dir(tmp_path: Path) -> Path:
    (tmp_path / "providers.json").write_text(PROVIDERS_JSON5)
    (tmp_path / "models_fallback_rules.json").write_text(RULES_JSON5)
    return tmp_path
