"""Gateway entrypoint: ``python main.py``.

Counterpart of the reference's ``main.py:119-127`` uvicorn runner; here the
server is aiohttp. Settings come from ``.env`` / environment
(GATEWAY_PORT default 9100, GATEWAY_HOST, GATEWAY_API_KEY, FALLBACK_PROVIDER,
CONFIG_DIR, DB_DIR, LOGS_DIR, LOG_LEVEL, ...).
"""
from llmapigateway_tpu.server.app import run

if __name__ == "__main__":
    run()
