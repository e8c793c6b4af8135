import os

# Tests must see ONE device (only launch/dryrun.py forces 512). Keep any
# user-provided XLA_FLAGS but never the host-device override.
#
# The bitwise cross-engine parity tests need CPU code that never contracts
# a multiply and an add into one FMA: XLA fuses the reference and the
# sharded engine's programs differently, so an FMA in one and a rounded
# multiply-then-add in the other differ in the last bits. AVX is the
# widest x86 instruction set without FMA.
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "host_platform_device_count" not in f
         and "xla_cpu_max_isa" not in f]
os.environ["XLA_FLAGS"] = " ".join(flags + ["--xla_cpu_max_isa=AVX"])

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
