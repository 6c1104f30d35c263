"""graftlint: per-rule fixtures (each rule fires on a known-bad snippet and
stays silent on a known-good one), suppression semantics, the CLI, and the
tier-1 meta-test that the live package tree is clean — so every future PR
inherits the async-hygiene / tracer-safety / lock-discipline gate."""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import llmapigateway_tpu
from llmapigateway_tpu.analysis import (ALL_RULES, RULES_BY_NAME,
                                        analyze_file, analyze_source,
                                        iter_python_files)

PACKAGE_DIR = Path(llmapigateway_tpu.__file__).parent


def lint(src: str, path: str) -> list:
    return analyze_source(textwrap.dedent(src), path, ALL_RULES)


def rules_hit(src: str, path: str) -> set[str]:
    return {f.rule for f in lint(src, path)}


# -- fixture pairs per rule ---------------------------------------------------

ASYNC_BAD = """
    import time, requests, sqlite3, jax

    async def handler(request):
        time.sleep(0.5)
        requests.get("http://upstream")
        conn = sqlite3.connect("db.sqlite")
        jax.block_until_ready(arr)
        n = arr.item()
        body = open("f.txt").read()
        p.read_text()
        v = float(jnp.sum(arr))
"""

ASYNC_GOOD = """
    import asyncio

    async def handler(request):
        await asyncio.sleep(0.5)
        text = await asyncio.to_thread(path.read_text)
        n = await asyncio.to_thread(int, "7")

        def blocking_payload():        # worker-thread body: blocking is fine
            import time
            time.sleep(1)
            return open("f.txt").read()
        return await asyncio.to_thread(blocking_payload)

    def sync_helper():                  # not on the event loop
        import time
        time.sleep(1)
"""


def test_async_blocking_fires_on_bad():
    # device-sync-discipline overlaps on the JAX-sync subset (its own
    # fixtures assert that separation); this test pins async-blocking's
    # coverage specifically.
    findings = [f for f in lint(ASYNC_BAD, "server/fixture.py")
                if f.rule == "async-blocking"]
    # Every listed blocking primitive is caught.
    msgs = " | ".join(f.message for f in findings)
    for needle in ("time.sleep", "requests", "sqlite3",
                   "block_until_ready", ".item()", "open()", "file read",
                   "float()"):
        assert needle in msgs, needle
    assert len(findings) == 8


def test_async_blocking_silent_on_good():
    assert rules_hit(ASYNC_GOOD, "server/fixture.py") == set()


def test_async_blocking_scoped_to_serving_dirs():
    # The same bad code outside server/routing/providers is not this
    # rule's business (the engine offloads differently).
    assert "async-blocking" not in rules_hit(ASYNC_BAD, "parallel/fixture.py")


TRACER_BAD = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def step(cache, x):
        if jnp.any(x > 0):                 # traced branch
            x = x + 1
        host = np.asarray(x)               # host sync
        s = float(jnp.sum(x))              # concretization
        return cache, x

    def scan_body(carry, x):
        v = jax.device_get(x)              # host sync in scan body
        return carry, v

    out = jax.lax.scan(scan_body, 0, xs)
"""

TRACER_GOOD = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def step(x, greedy: bool = False):
        if greedy:                          # static Python config: legal
            return jnp.argmax(x, axis=-1)
        y = jnp.where(x > 0, x, 0)          # traced select: legal
        for k in range(4):                  # static iteration: legal
            y = y + k
        return y

    def host_helper(x):                     # not traced: host ops legal
        arr = np.asarray(x)
        if arr.any():
            return float(arr.sum())
        return 0.0
"""


def test_tracer_hazard_fires_on_bad():
    findings = lint(TRACER_BAD, "engine/fixture.py")
    assert {f.rule for f in findings} == {"tracer-hazard"}
    msgs = " | ".join(f.message for f in findings)
    for needle in ("Python `if`", "np.asarray", "float()", "device_get"):
        assert needle in msgs, needle
    assert len(findings) == 4


def test_tracer_hazard_silent_on_good():
    assert rules_hit(TRACER_GOOD, "engine/fixture.py") == set()


def test_tracer_hazard_scoped_to_engine_and_ops():
    assert "tracer-hazard" not in rules_hit(TRACER_BAD, "server/fixture.py")


LOCK_BAD = """
    import asyncio
    import threading

    class Service:
        def __init__(self):
            self._lock = threading.Lock()
            self._table = {}        # guarded-by: _lock
            self._jobs = []         # guarded-by: loop

        def unlocked_write(self, k, v):
            self._table[k] = v              # mutation outside the lock

        def unlocked_method_mutation(self):
            self._table.update(a=1)         # mutator call outside the lock

        async def blocks_the_loop(self):
            with self._lock:
                await asyncio.sleep(1)      # await under a threading lock

        async def dispatch(self):
            await asyncio.to_thread(self._worker)

        def _worker(self):
            self._jobs.append(1)            # loop-only state from a thread
"""

LOCK_GOOD = """
    import asyncio
    import threading

    class Service:
        def __init__(self):
            self._lock = threading.Lock()
            self._alock = asyncio.Lock()
            self._table = {}        # guarded-by: _lock
            self._cache = {}        # guarded-by: _alock
            self._jobs = []         # guarded-by: loop
            self._table["init"] = True      # __init__: object not escaped

        def locked_write(self, k, v):
            with self._lock:
                self._table[k] = v
                self._table.update(a=1)

        async def async_locked(self, k, v):
            async with self._alock:
                self._cache[k] = v

        async def held_across_await_is_fine_for_asyncio_lock(self):
            async with self._alock:
                await asyncio.sleep(0)

        async def loop_side(self):
            self._jobs.append(1)            # event-loop thread: fine
            await asyncio.to_thread(self._worker)

        def _worker(self):
            return len(self._jobs)          # read-only from the thread
"""


def test_lock_discipline_fires_on_bad():
    findings = lint(LOCK_BAD, "db/fixture.py")
    assert {f.rule for f in findings} == {"lock-discipline"}
    msgs = " | ".join(f.message for f in findings)
    assert "mutated outside a `with self._lock`" in msgs
    assert "await while holding a threading.Lock" in msgs
    assert "worker-thread-reachable method _worker()" in msgs
    assert len(findings) == 4


def test_lock_discipline_silent_on_good():
    assert rules_hit(LOCK_GOOD, "db/fixture.py") == set()


LIFECYCLE_BAD = """
    class Recovery:
        def force_serving(self, engine):
            engine.supervisor._lc_state = "serving"

        def park(self, sup):
            setattr(sup, "_lc_state", "failed")
"""

LIFECYCLE_GOOD = """
    class Recovery:
        def force_serving(self, engine):
            engine.supervisor.transition("serving", "recovered")

        def read_state(self, sup):
            return sup._lc_state            # reads are fine
"""


def test_lifecycle_discipline_fires_on_bad():
    findings = [f for f in lint(LIFECYCLE_BAD, "engine/fixture.py")
                if f.rule == "lifecycle-discipline"]
    msgs = " | ".join(f.message for f in findings)
    assert "direct write to '_lc_state'" in msgs
    assert "setattr on '_lc_state'" in msgs
    assert len(findings) == 2


def test_lifecycle_discipline_silent_on_good():
    assert "lifecycle-discipline" not in rules_hit(
        LIFECYCLE_GOOD, "engine/fixture.py")


def test_lifecycle_discipline_exempts_supervisor_module():
    # The state machine's own module seeds and stores _lc_state — that
    # is the ONE place allowed to.
    findings = [f for f in lint(LIFECYCLE_BAD,
                                "reliability/supervisor.py")
                if f.rule == "lifecycle-discipline"]
    assert findings == []


SECRET_BAD = """
    import logging
    logger = logging.getLogger(__name__)

    def report(self, details):
        logger.info("using key %s", self.api_key)
        logger.warning(f"auth header: {authorization}")
        logger.error("provider", extra={"k": details.apikey})
"""

SECRET_GOOD = """
    import logging
    logger = logging.getLogger(__name__)

    def report(self, headers):
        logger.info("provider %s ready", self.name)
        logger.info("headers %s", mask_headers(headers))
        logger.info("usage: %d prompt_tokens, %d max_tokens", 3, 4)
        if self.api_key:                      # non-log use: fine
            self._client.headers["Authorization"] = f"Bearer {self.api_key}"
"""


def test_secret_hygiene_fires_on_bad():
    findings = lint(SECRET_BAD, "providers/fixture.py")
    assert {f.rule for f in findings} == {"secret-hygiene"}
    assert len(findings) == 3       # positional, f-string, extra= dict


def test_secret_hygiene_silent_on_good():
    assert rules_hit(SECRET_GOOD, "providers/fixture.py") == set()


SSE_BAD = """
    async def frames():
        yield "event: message\\n"            # unterminated, no data line
        yield b"raw payload\\n\\n"           # unframed payload line
        yield f"{payload}\\n\\n"             # interpolation without framing
"""

SSE_GOOD = """
    SSE_DONE = "[DONE]"

    async def frames():
        yield b"data: {}\\n\\n"
        yield "data: [DONE]\\n\\n"
        yield f"data: {payload}\\n\\n"
        yield ": keep-alive\\n\\n"
        yield ("data: ok\\n\\n").encode()
        yield format_sse({"choices": []})     # sanctioned constructor
        yield frame_bytes                     # dynamic: not lexically checkable
"""


def test_sse_protocol_fires_on_bad():
    findings = lint(SSE_BAD, "utils/sse.py")
    assert {f.rule for f in findings} == {"sse-protocol"}
    assert len(findings) == 3


def test_sse_protocol_silent_on_good():
    assert rules_hit(SSE_GOOD, "utils/sse.py") == set()


def test_sse_protocol_scoped_to_streaming_files():
    assert "sse-protocol" not in rules_hit(SSE_BAD, "engine/fixture.py")


TIMEOUT_BAD = """
    import httpx

    class P:
        def __init__(self):
            self._client = httpx.AsyncClient()            # no default timeout

        async def complete(self, url, payload):
            resp = await self._client.post(url, json=payload)
            req = self._client.build_request("POST", url, json=payload)
            inventory = await self._client.get(url)
            return resp, req, inventory
"""

TIMEOUT_GOOD = """
    import httpx

    TIMEOUT = httpx.Timeout(300.0, connect=60.0)

    class P:
        def __init__(self, client=None):
            self._client = client or httpx.AsyncClient(timeout=TIMEOUT)

        async def complete(self, url, payload):
            resp = await self._client.post(url, json=payload, timeout=TIMEOUT)
            req = self._client.build_request("POST", url, json=payload,
                                             timeout=TIMEOUT)
            sent = await self._client.send(req, stream=True)   # rides req
            model = payload.get("model", "")                   # dict .get: not httpx
            return resp, sent, model
"""


def test_timeout_discipline_fires_on_bad():
    findings = lint(TIMEOUT_BAD, "providers/fixture.py")
    assert {f.rule for f in findings} == {"timeout-discipline"}
    msgs = " | ".join(f.message for f in findings)
    assert "httpx.AsyncClient" in msgs
    assert "post()" in msgs and "build_request()" in msgs and "get()" in msgs
    assert len(findings) == 4


def test_timeout_discipline_silent_on_good():
    assert rules_hit(TIMEOUT_GOOD, "providers/fixture.py") == set()


def test_timeout_discipline_scoped_to_providers():
    assert "timeout-discipline" not in rules_hit(TIMEOUT_BAD,
                                                 "server/fixture.py")


METRIC_BAD = """
    def setup(registry, tracer):
        registry.counter("Gateway_Requests_Total", "not snake_case")
        registry.gauge("gateway_queue_depth", "no unit suffix")
        registry.histogram("gateway_latency_ms", "wrong unit suffix")
        sp = begin_span("router.attempt", layer="router")
        sp2 = tracer.begin_span("provider.call")
"""

METRIC_GOOD = """
    def setup(registry):
        registry.counter("gateway_http_requests_total", "completions")
        registry.gauge("gateway_engine_queue_wait_seconds", "admission wait")
        registry.histogram("gateway_provider_attempt_duration_seconds", "rt")
        registry.gauge("gateway_engine_kv_occupancy_ratio", "pool use")
        registry.gauge("gateway_engine_step_hbm_bytes", "bytes/step")
        registry.counter(dynamic_name, "non-literal name: not checkable")
        with span("router.attempt", layer="router"):
            pass
        payload.get("model")            # unrelated .get: not a factory
"""


def test_metric_discipline_fires_on_bad():
    findings = lint(METRIC_BAD, "server/fixture.py")
    assert {f.rule for f in findings} == {"metric-discipline"}
    msgs = " | ".join(f.message for f in findings)
    assert "not snake_case" in msgs
    assert "lacks a unit suffix" in msgs
    assert "begin_span" in msgs
    # 3 bad names + 2 bare begin_span calls (bare and method form).
    assert len(findings) == 5


def test_metric_discipline_silent_on_good():
    assert rules_hit(METRIC_GOOD, "server/fixture.py") == set()


def test_metric_discipline_exempts_the_tracer_module():
    src = """
    def span(name, layer="gateway", **attrs):
        sp = begin_span(name, layer, **attrs)
        return sp
    """
    assert "metric-discipline" not in rules_hit(src, "obs/trace.py")
    # The same primitive call anywhere else is a finding.
    assert "metric-discipline" in rules_hit(src, "obs/other.py")


EXC_BAD = """
    async def route(self, request):
        try:
            return await self._attempt(request)
        except:                             # bare: traps CancelledError
            return None

    def drain(self):
        try:
            self._flush()
        except Exception:
            pass                            # swallowed silently

    def probe(self):
        try:
            self._ping()
        except (ValueError, Exception):     # broad via tuple, no handling
            return None
"""

EXC_GOOD = """
    import logging
    logger = logging.getLogger(__name__)

    def narrow(self):
        try:
            self._flush()
        except ValueError:                  # specific: the classification
            pass

    def logged(self):
        try:
            self._flush()
        except Exception:
            logger.exception("flush failed (ignored)")

    def reraised(self):
        try:
            self._flush()
        except Exception as e:
            raise RuntimeError("flush") from e

    def typed(self):
        try:
            self._flush()
        except Exception as e:
            return CompletionError(str(e))

    def typed_overload(self):
        try:
            self._admit()
        except Exception as e:
            raise EngineOverloaded(str(e))
"""


def test_exception_hygiene_fires_on_bad():
    findings = lint(EXC_BAD, "routing/fixture.py")
    assert {f.rule for f in findings} == {"exception-hygiene"}
    msgs = " | ".join(f.message for f in findings)
    assert "bare `except:`" in msgs
    assert "swallows the failure silently" in msgs
    assert len(findings) == 3


def test_exception_hygiene_silent_on_good():
    assert rules_hit(EXC_GOOD, "providers/fixture.py") == set()


def test_exception_hygiene_scoped_to_serving_and_engine():
    # server/ (and everywhere else outside routing/providers/engine) is
    # not this rule's business.
    assert "exception-hygiene" not in rules_hit(EXC_BAD, "server/fixture.py")
    assert "exception-hygiene" in rules_hit(EXC_BAD, "engine/fixture.py")


DEVICE_SYNC_BAD = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    async def handler(request):
        arr = request.app["arr"]
        arr.block_until_ready()
        host = np.asarray(jnp.sum(arr))
        v = float(jnp.max(arr))
        return host, v
"""

DEVICE_SYNC_GOOD = """
    import asyncio
    import numpy as np

    async def handler(request):
        arr = request.app["arr"]
        host = await asyncio.to_thread(np.asarray, arr)
        counts = np.asarray(request.app["host_list"])   # host data: no jnp
        return host, counts

    async def documented(request):  # device-sync: ok — replicated scalar
        return float(jnp.max(request.app["gauge"]))
"""


def test_device_sync_fires_on_bad():
    findings = [f for f in lint(DEVICE_SYNC_BAD, "server/fixture.py")
                if f.rule == "device-sync-discipline"]
    msgs = " | ".join(f.message for f in findings)
    for needle in (".block_until_ready()", "np.asarray()", "float()"):
        assert needle in msgs, needle
    assert len(findings) == 3


def test_device_sync_silent_on_good():
    # to_thread dispatch, host-only asarray, and the `# device-sync: ok`
    # marker all pass (async-blocking stays silent too: to_thread
    # payloads are the sanctioned offload).
    hit = rules_hit(DEVICE_SYNC_GOOD, "server/fixture.py")
    assert "device-sync-discipline" not in hit


def test_device_sync_scoped_to_serving_dirs():
    assert "device-sync-discipline" not in rules_hit(
        DEVICE_SYNC_BAD, "engine/fixture.py")


# -- suppressions -------------------------------------------------------------

def test_trailing_suppression_is_line_scoped():
    src = """
    import time

    async def handler(request):
        time.sleep(0.1)  # graftlint: disable=async-blocking
        time.sleep(0.2)
    """
    findings = lint(src, "server/fixture.py")
    assert len(findings) == 1
    assert findings[0].message.startswith("time.sleep()")


def test_standalone_suppression_is_file_scoped():
    src = """
    # graftlint: disable=async-blocking
    import time

    async def handler(request):
        time.sleep(0.1)
        time.sleep(0.2)
    """
    assert lint(src, "server/fixture.py") == []


def test_disable_all_and_unknown_rule_name():
    # The stale suppression is assembled so linting THIS file doesn't see it.
    src = """
    # graftlint: disable=all
    import time

    async def handler(request):
        time.sleep(0.1)  # graft""" + """lint: disable=no-such-rule
    """
    findings = lint(src, "server/fixture.py")
    # The blocking call is suppressed, but the stale suppression name is
    # itself reported — typos can't rot silently.
    assert [f.rule for f in findings] == ["graftlint-meta"]
    assert "no-such-rule" in findings[0].message


def test_syntax_error_is_a_finding():
    findings = lint("def broken(:\n    pass\n", "server/fixture.py")
    assert [f.rule for f in findings] == ["parse-error"]


# -- CLI ----------------------------------------------------------------------

def test_cli_json_output_and_exit_codes(tmp_path):
    bad = tmp_path / "server"
    bad.mkdir()
    (bad / "handler.py").write_text(
        "import time\nasync def h(r):\n    time.sleep(1)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "llmapigateway_tpu.analysis",
         str(tmp_path), "--format", "json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["count"] == 1
    assert doc["findings"][0]["rule"] == "async-blocking"

    (bad / "handler.py").write_text(
        "import asyncio\nasync def h(r):\n    await asyncio.sleep(1)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "llmapigateway_tpu.analysis", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    assert "clean" in proc.stdout


def test_cli_rule_catalog_lists_all_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "llmapigateway_tpu.analysis", "--list-rules"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for name in RULES_BY_NAME:
        assert name in proc.stdout


# -- the tier-1 gate ----------------------------------------------------------

def test_live_codebase_is_clean():
    """The whole shipped package passes graftlint with zero unsuppressed
    findings — the invariant gate every future PR inherits. On failure the
    assertion message carries the findings, so the CI log is the report."""
    findings = []
    for path in iter_python_files(PACKAGE_DIR):
        findings.extend(analyze_file(path, ALL_RULES))
    rendered = "\n".join(f.render() for f in findings)
    assert not findings, f"graftlint findings in the live tree:\n{rendered}"


def test_live_codebase_branches_on_no_kv_layout():
    """The KV cache is a page pool and nothing else (PR 49): no condition
    anywhere in the package reads ``kv_layout`` or a ``paged`` flag — a
    second layout would come back as one of these first."""
    import ast
    for path in iter_python_files(PACKAGE_DIR):
        for node in ast.walk(ast.parse(Path(path).read_text())):
            test = getattr(node, "test", None) if isinstance(
                node, (ast.If, ast.IfExp, ast.While)) else (
                node if isinstance(node, ast.Compare) else None)
            if test is None:
                continue
            read = {n.attr if isinstance(n, ast.Attribute) else n.id
                    for n in ast.walk(test)
                    if isinstance(n, (ast.Attribute, ast.Name))}
            assert not read & {"kv_layout", "paged"}, (path, node.lineno)


def test_live_codebase_program_clean():
    """graftlint v2's whole-program pass (symbol table + call graph +
    dataflow: transitive async-blocking, guarded-by inference, httpx
    timeout flow) over the live tree: zero unsuppressed findings. This is
    the gate that keeps 'one transitive call through a sync helper' from
    quietly re-introducing an event-loop stall (ISSUE 5)."""
    from llmapigateway_tpu.analysis import analyze_program
    findings = analyze_program([PACKAGE_DIR])
    rendered = "\n".join(f.render() for f in findings)
    assert not findings, \
        f"whole-program graftlint findings in the live tree:\n{rendered}"


def test_live_codebase_program_pass_engages():
    """The clean result above must not be vacuous: the program pass must
    actually resolve cross-module chains on the live tree (entries exist,
    the call graph links server/ handlers into config/, providers/ into
    the engine)."""
    from llmapigateway_tpu.analysis import iter_python_files, summarize_source
    from llmapigateway_tpu.analysis.program import Program
    summaries = {}
    for path in iter_python_files(PACKAGE_DIR):
        s = summarize_source(path.read_text(), path)
        if s is not None:
            summaries[s["relpath"]] = s
    program = Program(summaries)
    # The chain that motivated the pass: an async config handler resolving
    # into ConfigLoader.read_raw across modules.
    tgt = program.resolve_call("server.config_api", "get_rules_text",
                               "?.read_raw")
    assert tgt == ("config.loader", "ConfigLoader.read_raw")
    # Guard annotations visible tree-wide.
    guards = program._guard_index()
    assert guards["InferenceEngine"]["_running"] == "loop"
    assert guards["ConfigLoader"]["_providers"] == "_lock"
    # Thread-dispatch reachability sees the engine's worker offloads.
    reach = program._thread_reachable()
    assert any(ql.startswith("InferenceEngine.")
               for _, ql in reach), "engine worker dispatches must resolve"


def test_live_codebase_annotations_engage():
    """The guarded-by convention is actually present in the five files the
    lock-discipline rule documents — the clean result above must not be
    vacuous."""
    for rel in ("engine/engine.py", "db/usage.py", "db/rotation.py",
                "config/loader.py", "routing/router.py"):
        text = (PACKAGE_DIR / rel).read_text()
        assert "guarded-by:" in text, rel
