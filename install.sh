#!/bin/sh
# Install keto-tpu into the current Python environment (the reference's
# install.sh downloads a prebuilt Go binary; a JAX framework installs as
# a Python package instead).
#
# Usage:
#   ./install.sh            # CPU jax (works everywhere; slow)
#   ./install.sh tpu        # TPU VM: jax with libtpu
set -e

here="$(cd "$(dirname "$0")" && pwd)"
target="${1:-cpu}"

case "$target" in
  cpu) jax_pkg="jax[cpu]==0.9.0" ;;
  tpu) jax_pkg="jax[tpu]==0.9.0" ;;
  *) echo "usage: $0 [cpu|tpu]" >&2; exit 2 ;;
esac

python -m pip install "$here" "$jax_pkg" grpcio protobuf pyyaml

echo "installed: $(keto-tpu version)"
echo "try: keto-tpu serve -c contrib/cat-videos-example/keto.yml"
