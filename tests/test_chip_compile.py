"""Compile rehearsal for the attached chip: the served match programs,
at the shapes a 1M-wildcard-filter table pads to, go through the v5e
compiler without a chip (on-chip-measurement guide, section 2.3).

Nothing runs here, so a pass says nothing about answers or times — it
says the chip's compiler accepts the program ``chip_smoke.py`` will
dispatch.  The topology is described inside a module-scoped fixture
(only the xdist worker that is handed this file loads libtpu); keep
every chip-describing test in THIS file.
"""

import pytest

from emqx_tpu.config import Config
from emqx_tpu.ops.kernel_cache import MatchKernelCache
from emqx_tpu.ops.match_kernel import SERVE_FLAT_MULT

_CFG = Config()
B = _CFG.get("tpu.batch_size")            # 2048
A = _CFG.get("tpu.active_slots")          # 16
K = _CFG.get("tpu.max_matches")           # 128
LANES = (_CFG.get("tpu.short_depth"), _CFG.get("tpu.max_levels"))  # 4, 16
# what IncrementalNfa/NativeNfa.shape_key() reports for chip_smoke.py's
# table (1,018,199 filters -> 1,894,967 states, both pow2-padded)
S = HB = 1 << 21
# a table inside the Pallas kernels' own VMEM budget (pallas_match.py)
S_VMEM = HB_VMEM = 1 << 15
D_VMEM = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a described-device compile lands in the persistent cache but can
    # never be read back without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(key, sharding, **steer):
    fn, args, static = MatchKernelCache.lowering(key, sharding=sharding)
    static.update(steer)
    return fn.lower(*args, **static).compile()


def _key(depth, *, flat, backend="hash", s=S, hb=HB):
    return MatchKernelCache.key(
        (B, depth), s, hb, active_slots=A, max_matches=K,
        compact_output=True, flat_cap=SERVE_FLAT_MULT * B if flat else 0,
        backend=backend)


@pytest.mark.parametrize("depth", [LANES[1], LANES[0]])
def test_nfa_match_compiles_for_v5e(one_chip, depth):
    """The reference program, compact (B, K) output, both lanes."""
    compiled = _compile(_key(depth, flat=False), one_chip)
    mem = compiled.memory_analysis()
    # the two table operands alone are 2^21 * (16 + 32) bytes
    assert mem.argument_size_in_bytes >= S * 16 + HB * 32


@pytest.mark.parametrize("depth", [LANES[1], LANES[0]])
@pytest.mark.parametrize("backend", ["hash", "join"])
def test_served_program_compiles_for_v5e(one_chip, backend, depth):
    """What every single-chip serve path dispatches (``DeviceNfa.serve``,
    with or without a kernel cache): ONE (B + flat_cap,) output."""
    compiled = _compile(_key(depth, flat=True, backend=backend), one_chip)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 4 * (B + SERVE_FLAT_MULT * B)
    assert mem.argument_size_in_bytes >= S * 16


# Mosaic's verdict on the in-VMEM table gathers, taken 2026-09-26 with
# jax 0.9.0 / libtpu 0.0.34.  strict: the day a repair lands, this says so.
_MOSAIC = "Shape mismatch in input, indices and output"


@pytest.mark.xfail(strict=True, raises=ValueError, reason=_MOSAIC)
def test_pallas_join_match_flat_compiles_for_v5e(one_chip):
    # lowering() reads jax.default_backend() (the CPU here) and would
    # pick interpret mode: steer it to the real lowering in the test
    _compile(_key(D_VMEM, flat=True, backend="join-pallas",
                  s=S_VMEM, hb=HB_VMEM), one_chip, interpret=False)


@pytest.mark.xfail(strict=True, raises=ValueError, reason=_MOSAIC)
def test_pallas_small_match_compiles_for_v5e(one_chip):
    from emqx_tpu.ops.pallas_match import pallas_small_match

    _fn, args, _static = MatchKernelCache.lowering(
        _key(D_VMEM, flat=True, s=S_VMEM, hb=HB_VMEM), sharding=one_chip)
    pallas_small_match.lower(
        *args, depth=D_VMEM, active_slots=A, interpret=False).compile()
