"""The chip benchmark's tests run on the CPU, four virtual devices standing
in for a four-chip host; the harness's modules import from the directory
above."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
for path in (os.path.dirname(HERE), os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
