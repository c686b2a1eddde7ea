# OpsAgent-TPU agent/API image.
#
# Two-stage build: a toolchain stage compiles the native constrained-decoding
# FSM matcher (opsagent_tpu/native/fsm_matcher.cc), then a slim runtime image
# carries the Python package, kubectl + jq for the tool layer, and the
# dedicated python-tool venv (the `python` tool execs scripts inside
# /app/k8s/python-cli/k8s-env; reference parity: /root/reference/Dockerfile:30-44,
# pkg/tools/python.go:31).
#
# This image runs the AGENT layers (CLI / REST server / tools). The TPU
# serving engine runs on TPU nodes from the same image via
#   `opsagent serve-engine` (see deploy/kubernetes/serving-engine.yaml);
# on CPU-only pods the agent talks to it over the OpenAI wire format.

FROM python:3.12-slim AS builder

RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /src
COPY pyproject.toml ./
COPY opsagent_tpu ./opsagent_tpu

# Pre-build the native FSM matcher so the runtime image needs no compiler.
RUN python -c "from opsagent_tpu.native import build_native; build_native()"


FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        ca-certificates curl bash jq tzdata \
    && rm -rf /var/lib/apt/lists/*

# kubectl for the kubectl tool (pinned; the tool shells out via `bash -c`).
ARG KUBECTL_VERSION=v1.30.0
RUN curl --retry 3 -fsSLo /usr/local/bin/kubectl \
        "https://dl.k8s.io/release/${KUBECTL_VERSION}/bin/linux/amd64/kubectl" \
    && chmod +x /usr/local/bin/kubectl

WORKDIR /app
COPY pyproject.toml ./
COPY opsagent_tpu ./opsagent_tpu
COPY configs ./configs
COPY --from=builder /src/opsagent_tpu/native/_native-*.so ./opsagent_tpu/native/

# Agent runtime deps. jax[cpu] serves the agent layers; TPU pods get the
# TPU jaxlib from their node image / a requirements overlay.
RUN pip install --no-cache-dir "jax[cpu]" numpy && \
    pip install --no-cache-dir ".[tokens]"

# Sandbox venv for the `python` tool (kept separate from the app runtime so
# model-generated scripts cannot import the server's own dependencies).
RUN python -m venv /app/k8s/python-cli/k8s-env && \
    /app/k8s/python-cli/k8s-env/bin/pip install --no-cache-dir \
        kubernetes==29.0.0 pyyaml==6.0.1 pandas==2.2.1 && \
    ln -s /app/k8s /root/k8s

RUN useradd -u 1000 -m opsagent && mkdir -p /app/logs && \
    chown -R opsagent:opsagent /app/logs

ENV PYTHONUNBUFFERED=1
EXPOSE 8080
ENTRYPOINT ["opsagent"]
CMD ["server", "--port", "8080"]
