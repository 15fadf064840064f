"""The tests beside the benchmark run on the CPU, asked for by name,
with four virtual devices for the cell that spans chips. Run them as
``python -m pytest benchmarks/tests -q -p no:cacheprovider`` from the
root; they are not part of the repo's tier-1 suite."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
