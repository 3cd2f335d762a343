"""Give the benchmark's CPU tests four host devices, as the four-chip
cell has four chips: its spec builds one unit per device, and a test
that plants a fault in every unit but the first needs more than one.
The one-chip cell's two units then sit on two host devices."""
import os

_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_FLAG}"
