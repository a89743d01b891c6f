import os

# the benchmark's tests run on the CPU: a test run on a chip host must never
# take the chip, which belongs to one process at a time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
