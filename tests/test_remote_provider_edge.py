"""Edge cases in the remote provider's streaming path (code-review findings)."""
import asyncio
import gc
import json

from aiohttp import web
from aiohttp.test_utils import TestServer

from llmapigateway_tpu.providers.base import CompletionRequest
from llmapigateway_tpu.providers.remote_http import RemoteHTTPProvider
from llmapigateway_tpu.server.usage_capture import UsageCollector
from llmapigateway_tpu.utils.sse import SSEParser


class Recorder(UsageCollector):
    def __init__(self):
        super().__init__(provider="p", model="m")


async def _collect(provider, payload):
    obs = Recorder()
    result, error = await provider.complete(
        CompletionRequest(payload=payload, stream=True), obs)
    frames = []
    if result is not None:
        async for chunk in result.frames:
            p = SSEParser()
            frames.extend(f.data for f in p.feed(chunk))
    return frames, error, obs


async def _settle():
    """A stream closed before its end leaves httpx's inner byte-stream
    generators to the collector, whose finalizer closes each as a task on
    this loop. Let them run inside the test that made them: when this
    file's last (synchronous) test ends a worker's session nothing turns
    the loop again, and the sanitizer reports the tasks as leaked."""
    gc.collect()
    for _ in range(3):
        await asyncio.sleep(0)


async def test_tiny_response_data_and_done_in_one_chunk(tmp_path):
    """A data frame + [DONE] arriving in one TCP chunk must commit, not be
    discarded as 'stream ended with no data'."""
    async def handler(request):
        resp = web.StreamResponse(
            status=200, headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        body = {"id": "x", "choices": [{"index": 0,
                                        "delta": {"content": "short"},
                                        "finish_reason": "stop"}]}
        # Single write: everything in one chunk.
        await resp.write(f"data: {json.dumps(body)}\n\ndata: [DONE]\n\n".encode())
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_post("/v1/chat/completions", handler)
    server = TestServer(app)
    await server.start_server()
    try:
        provider = RemoteHTTPProvider(
            "t", f"http://{server.host}:{server.port}/v1")
        frames, error, obs = await _collect(provider, {"model": "m", "stream": True})
        assert error is None
        assert frames[-1] == "[DONE]"
        assert "".join(obs._text) == "short"
        await provider.close()
    finally:
        await server.close()
        await _settle()


async def test_done_with_no_data_is_error(tmp_path):
    async def handler(request):
        resp = web.StreamResponse(
            status=200, headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_post("/v1/chat/completions", handler)
    server = TestServer(app)
    await server.start_server()
    try:
        provider = RemoteHTTPProvider(
            "t", f"http://{server.host}:{server.port}/v1")
        frames, error, obs = await _collect(provider, {"model": "m", "stream": True})
        assert error is not None and "no data" in error.detail
        await provider.close()
    finally:
        await server.close()
        await _settle()


def test_format_sse_multiline_spec_compliant():
    from llmapigateway_tpu.utils.sse import format_sse
    out = format_sse("line1\nline2")
    assert out == b"data: line1\ndata: line2\n\n"
    # Round-trips through the parser as a joined multi-line event.
    p = SSEParser()
    frames = list(p.feed(out))
    assert frames[0].data == "line1\nline2"
