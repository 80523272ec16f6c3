import os
import re

# 8 fake CPU devices so the multi-device tests can build real meshes on a
# single host.  Must be set before jax initializes; single-device tests
# are unaffected (unsharded jit still runs on device 0).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

# The whole suite runs with strict donation: a strict_jit site whose
# compiled program does not alias a donated buffer raises instead of
# silently doubling cache/optimizer memory (core.jitutil).
os.environ.setdefault("REPRO_STRICT", "1")

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.configs import REGISTRY, reduced  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def reduced_cfg(name):
    return reduced(REGISTRY[name])


# Instructions that move a whole KV pool (or one layer of it) instead of
# writing it in place: the layer loop's slices, restacks, fresh ys
# buffers and layout copies.
POOL_MOVES = {"copy", "dynamic-update-slice", "broadcast", "concatenate"}
_HLO_INSTR = re.compile(r"= *\w+\[([\d,]*)\]\S* ([\w-]+)\(")


def hlo_instructions(text):
    """(shape, opcode) of every array-valued instruction in compiled HLO
    text."""
    return [(tuple(int(d) for d in dims.split(",") if d), op)
            for dims, op in _HLO_INSTR.findall(text)]
