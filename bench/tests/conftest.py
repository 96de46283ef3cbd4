import os

# the benchmark's tests run on the CPU; the harness refuses to measure there
os.environ.setdefault("JAX_PLATFORMS", "cpu")
