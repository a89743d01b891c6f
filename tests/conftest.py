import os
import sys

# tests run on the CPU: on an accelerator host a test run must never take
# the chip (it belongs to one process at a time); the dry-run sets its own
# XLA_FLAGS in-process, never here
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
