import os

# the benchmark's own tests run on the host CPU, like the repository's
os.environ.setdefault("JAX_PLATFORMS", "cpu")
