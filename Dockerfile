# keto-tpu serving image.
#
# The compute path is JAX: on a TPU VM, base this on a libtpu-enabled
# image (or `pip install "jax[tpu]==0.9.0"` in a derived stage) and the engine
# picks the chips up automatically; this default build serves on CPU —
# identical API surface, the device engine just compiles for the host.
# The reference ships a static Go binary in a scratch image; a JAX
# runtime needs a Python base instead (parity delta, documented).
FROM python:3.12-slim AS base

WORKDIR /opt/keto-tpu
COPY pyproject.toml README.md ./
COPY ketotpu ./ketotpu
COPY proto ./proto
COPY spec ./spec
RUN pip install --no-cache-dir . "jax[cpu]==0.9.0" grpcio protobuf pyyaml

# same default port layout as the reference (serve read 4466 / write
# 4467 / metrics 4468 / opl 4469)
EXPOSE 4466 4467 4468 4469

RUN useradd --create-home ory
USER ory
WORKDIR /home/ory

ENTRYPOINT ["keto-tpu"]
CMD ["serve", "-c", "/home/ory/keto.yml"]
