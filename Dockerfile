# parameter_server_tpu deployment image (ref /root/reference/Dockerfile:
# one container per node, role and cluster wiring injected via env).
#
# Build:   docker build -t parameter-server-tpu .
# One-box: docker run --rm parameter-server-tpu \
#            python -m parameter_server_tpu.apps.linear.main configs/rcv1.conf
# Cluster: run one container per host with the jax.distributed contract
#          (the analog of the reference's -scheduler/-my_node flags):
#            PS_COORDINATOR_ADDRESS=<host0>:<port>
#            PS_NUM_PROCESSES=<N>  PS_PROCESS_ID=<i>
#          On TPU hosts, pass the accelerator through (gcloud/k8s TPU
#          runtime) and leave JAX_PLATFORMS unset; off-TPU smoke runs use
#          JAX_PLATFORMS=cpu. See docker/ for local N-node compose.
FROM python:3.12-slim

# native host runtime (cpp/psnative.cc) builds with g++ at image build
# time, like the reference's `RUN make -j8`; on a host with another CPU the
# loader rebuilds it at first use
RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

# the compute stack; `jax[tpu]` swaps in the TPU PJRT plugin on TPU VMs
# (kept as the only knob — everything else is pure Python)
ARG JAX_EXTRA=""
RUN pip install --no-cache-dir "jax${JAX_EXTRA}" flax optax orbax-checkpoint chex einops numpy

WORKDIR /home/parameter_server_tpu
COPY parameter_server_tpu parameter_server_tpu
COPY configs configs
COPY script script
COPY chip_smoke.py setup.py Makefile ./
RUN make native

ENV PYTHONPATH=/home/parameter_server_tpu
# role dispatch comes from the conf + env, exactly like the reference's
# CMD build/linear -my_node "role:$my_role,..." pattern
CMD ["python", "-m", "parameter_server_tpu.apps.linear.main", "--help"]
