# TPU LLM gateway image. Counterpart of the reference's multi-stage
# python:3.12-slim Dockerfile (builder venv, non-root user, local configs
# excluded from the image), extended with a switchable base so the same file
# builds a proxy-only image (default) or a TPU serving image
# (BASE_IMAGE with libtpu + JAX preinstalled, e.g. a Cloud TPU base).
ARG BASE_IMAGE=python:3.12-slim

FROM ${BASE_IMAGE} AS builder
WORKDIR /build
RUN python -m venv /opt/venv
ENV PATH="/opt/venv/bin:$PATH"
COPY pyproject.toml ./
COPY llmapigateway_tpu ./llmapigateway_tpu
COPY main.py ./
RUN pip install --no-cache-dir .

FROM ${BASE_IMAGE}
ARG INSTALL_TPU_JAX=false
WORKDIR /app

# Non-root runtime user; db/logs live under /data (volume-mounted).
RUN groupadd -r gateway && useradd -r -g gateway -d /app gateway \
    && mkdir -p /data/db /data/logs /app/config \
    && chown -R gateway:gateway /app /data

COPY --from=builder /opt/venv /opt/venv
ENV PATH="/opt/venv/bin:$PATH"

# Optional: pull the TPU runtime into the venv (requires network at build
# time; proxy-only deployments skip this and never import JAX).
RUN if [ "$INSTALL_TPU_JAX" = "true" ]; then \
      pip install --no-cache-dir "jax[tpu]" \
        -f https://storage.googleapis.com/jax-releases/libtpu_releases.html; \
    fi

COPY --chown=gateway:gateway main.py ./
COPY --chown=gateway:gateway llmapigateway_tpu ./llmapigateway_tpu
COPY --chown=gateway:gateway docker/entrypoint.sh docker/healthcheck.py ./docker/
RUN chmod +x docker/entrypoint.sh \
    # Local configs/secrets must come from mounts, never the image:
    && rm -f .env providers.json models_fallback_rules.json

ENV CONFIG_DIR=/app/config \
    DB_DIR=/data/db \
    LOGS_DIR=/data/logs \
    GATEWAY_HOST=0.0.0.0 \
    GATEWAY_PORT=9100

USER gateway
EXPOSE 9100
HEALTHCHECK --interval=30s --timeout=5s --retries=3 \
    CMD ["python", "docker/healthcheck.py"]
ENTRYPOINT ["docker/entrypoint.sh"]
