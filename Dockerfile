# One parameterized image for the three runtime roles (the reference ships
# Dockerfile-ModelBuilder / -ModelServer / -Watchman; here a single image +
# ROLE build-arg keeps them byte-identical below the entrypoint, which is
# what the generated workflow manifests assume).
#
# Build:  docker build -t gordo-tpu-<role> --build-arg ROLE=<role> .
# Roles:  builder  -> `gordo-tpu build` (Argo injects env vars)
#         server   -> `gordo-tpu run-server`
#         watchman -> `gordo-tpu run-watchman`

FROM python:3.12-slim

ARG ROLE=builder
ENV GORDO_ROLE=${ROLE} \
    PYTHONUNBUFFERED=1

WORKDIR /opt/gordo
COPY pyproject.toml README.md ./
COPY gordo_components_tpu ./gordo_components_tpu

# This image runs on the CPU backend (plain `jax`). For TPU VMs install
# `jax[tpu]` instead and set JAX_PLATFORMS=tpu in the pod spec: a process
# that cannot get its chip then fails at start-up, where JAX would
# otherwise hand back the CPU without failing.
RUN pip install --no-cache-dir .

ENTRYPOINT ["python", "-m", "gordo_components_tpu.cli"]
