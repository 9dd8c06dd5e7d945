# kube-throttler-tpu daemon image (reference Dockerfile:1-20, recast for
# the Python/JAX runtime): a builder stage compiles the C++ selector
# engine and builds the wheel; the runtime stage carries only the
# installed package. Satisfies deploy/deployment.yaml's
# `image: kube-throttler-tpu:latest` — build with `make image` (or
# tools/build_image.sh, which the release workflow calls).
#
# The default CPU jax wheel serves clusters without accelerators; for TPU
# nodes build with  --build-arg JAX_EXTRA="jax[tpu]"  (pulls libtpu).

FROM python:3.12-slim AS builder
ARG JAX_EXTRA=""
WORKDIR /src

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ \
    && apt-get clean && rm -rf /var/lib/apt/lists/*

COPY pyproject.toml README.md ./
COPY kube_throttler_tpu/ kube_throttler_tpu/
RUN pip install --no-cache-dir build && python -m build --wheel --outdir /dist

# pre-compile the native selector engine for the runtime image so first
# import in a read-only container needs no toolchain
RUN g++ -O3 -std=c++17 -shared -fPIC \
    kube_throttler_tpu/native/ktnative.cpp -o /dist/_ktnative.so

FROM python:3.12-slim AS runtime
ARG JAX_EXTRA=""
COPY --from=builder /dist/ /tmp/wheel/
RUN pip install --no-cache-dir /tmp/wheel/*.whl ${JAX_EXTRA} \
    && cp /tmp/wheel/_ktnative.so \
        "$(python -c 'import kube_throttler_tpu.native as n; print(n.package_so_path())')" \
    && rm -rf /tmp/wheel

# non-root like the reference deployment expects; the flock lease and the
# native-build cache both live under XDG dirs, which we point at /tmp
RUN useradd --uid 65532 --create-home throttler
USER 65532
ENV XDG_CACHE_HOME=/tmp/.cache

EXPOSE 10259
ENTRYPOINT ["python", "-m", "kube_throttler_tpu.cli"]
CMD ["serve", "--host", "0.0.0.0", "--port", "10259"]
