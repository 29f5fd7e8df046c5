# Runtime image for the TPU-native rate-limit service.
# The reference builds a static Go binary into alpine (Dockerfile:1-15);
# here the image carries the Python package, the compiled native host codec,
# and the JAX stack. On TPU VMs, run with the host TPU runtime mounted
# (the libtpu wheel ships via the `jax[tpu]` extra).

FROM python:3.12-slim AS build

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make protobuf-compiler && \
    rm -rf /var/lib/apt/lists/*

WORKDIR /src
COPY pyproject.toml README.md Makefile requirements.txt ./
COPY native/ native/
COPY proto/ proto/
COPY api_ratelimit_tpu/ api_ratelimit_tpu/

# Pinned CPU wheels (requirements.txt is the single source CI shares);
# swap jax for `pip install 'jax[tpu]'` on TPU hosts.
RUN pip install --no-cache-dir -r requirements.txt && \
    make native

FROM python:3.12-slim

COPY --from=build /usr/local/lib/python3.12/site-packages /usr/local/lib/python3.12/site-packages
COPY --from=build /src/api_ratelimit_tpu /app/api_ratelimit_tpu
# the codec build is keyed by its source's hash (ops/native.py), so the
# source travels with the .so
COPY --from=build /src/native /app/native

WORKDIR /app
ENV PYTHONUNBUFFERED=1
# Reference port layout: 8080 HTTP, 8081 gRPC, 6070 debug (settings.go:13-16)
EXPOSE 8080 8081 6070

CMD ["python", "-m", "api_ratelimit_tpu.cmd.service_cmd"]
