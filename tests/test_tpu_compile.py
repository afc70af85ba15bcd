"""Compile every Pallas kernel variant of the main path for a described
TPU v5e chip (no chip attached): the TPU compiler refuses here what the
chip would refuse — unaligned lane slices, float iotas, 64-bit vector
types, scoped-VMEM overruns — which interpret mode never sees.

Shapes are real widths: 64 and 128 instance slots, the auto ``block_k``
ceiling, two arrival chunks; the single-function kernel gets two
lane-dense blocks of replica rows.  x64 stays on, as ``repro.core`` leaves it
for every caller.  The topology is described inside a module fixture
(never at import): only one process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.execution import _AUTO_BLOCK_K_MAX, Execution
from repro.kernels import faas_event_step as fe

R = 64  # 8 replica blocks of the lane-major kernels (fleet, par)
BK = _AUTO_BLOCK_K_MAX
K = 2 * BK
# the single-function kernel: rows on lanes, two 128-row blocks, two
# chunks at the auto block_k's ceiling
R_FAAS = 2 * fe.row_block(10**6)
BK_FAAS = Execution().resolved_block_k(10**6)
K_FAAS = 2 * BK_FAAS
W, G, E, Q, C_PAR = 24, 16, 4, 8, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, args, kw):
    assert jax.config.jax_enable_x64
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# fused draw distributions per fused variant: every kernel sampler once
FUSED_DISTS = {
    "fused": ("exp", "exp", "exp"),
    "fused_dists": ("gauss", "weibull", "lognorm"),
    "fused_reliability": ("exp", "pareto", "det"),
}


def _faas_variant(name, s, M):
    """Positional args and kwargs of one ``faas_sweep_pallas`` variant —
    each one a kernel a sweep launches."""
    R, K = R_FAAS, K_FAAS
    row, samp, state = _spec(s, R), _spec(s, R, K), _spec(s, R, M)
    kw = dict(t_end=row, skip=row, max_concurrency=M, block_k=BK_FAAS)
    if name in FUSED_DISTS:
        keys = _spec(s, R, 2, dtype=jnp.uint32)
        kw.update(
            fused_dists=FUSED_DISTS[name], fused_k=K, fused_keys=(keys,) * 3,
            fused_params=(_spec(s, R, 2),) * 3,
        )
        if name == "fused_reliability":
            kw.update(
                t_timeout=row, p_fail=row, fused_fail_keys=keys,
                reliability=True,
            )
        return (state, state, state, row, row, None, None, None), kw
    if name == "windows_prestamped":
        kw.update(window_bounds=_spec(s, R, W + 1), n_windows=W, prestamped=True)
    elif name == "grid_times":
        kw.update(grid_times=_spec(s, R, G), n_grid=G)
    elif name == "retries":
        kw.update(
            t_timeout=row, p_fail=row, fail_u=samp, is_first=samp,
            child_pos=samp, reliability=True, retries=True, prestamped=True,
        )
    elif name == "faults":
        kw.update(
            crash_rate=row, crash_u=samp, cap_edges=_spec(s, R, E - 1),
            cap_values=_spec(s, R, E),
        )
    else:
        assert name == "staged", name
    return (state, state, state, row, row, samp, samp, samp), kw


FAAS_VARIANTS = (
    "staged",
    "fused",
    "fused_dists",
    "fused_reliability",
    "windows_prestamped",
    "grid_times",
    "retries",
    "faults",
)


@pytest.mark.parametrize("slots", [64, 128])
@pytest.mark.parametrize("variant", FAAS_VARIANTS)
def test_faas_sweep_compiles_for_v5e(one_chip, variant, slots):
    args, kw = _faas_variant(variant, one_chip, slots)
    _compile(fe.faas_sweep_pallas, args, dict(kw, interpret=False))


def test_faas_sweep_compiles_at_an_explicit_lane_sized_chunk(one_chip):
    """An explicit ``Execution(block_k=10880)`` is honoured: three
    double-buffered [10880, 128] sample blocks pass the default scoped
    VMEM, so the launch raises the kernel's limit with them."""
    args, kw = _faas_variant("staged", one_chip, 64)
    kw.update(block_k=BK, interpret=False)
    _compile(fe.faas_sweep_pallas, args[:5] + (_spec(one_chip, R_FAAS, K),) * 3, kw)


@pytest.mark.parametrize("slots", [64, 128])
@pytest.mark.parametrize("variant", ["plain", "queue", "faults"])
def test_fleet_sweep_compiles_for_v5e(one_chip, variant, slots):
    s = one_chip
    row, samp = _spec(s, R), _spec(s, R, K)
    args = [row] * 5 + [samp] * 4
    kw = dict(slots=slots, block_k=BK, prestamped=True, interpret=False)
    if variant == "queue":
        kw["queue_depth"] = Q
    elif variant == "faults":
        kw.update(
            crash_rate=row, crash_u=samp, cap_edges=_spec(s, R, E - 1),
            cap_values=_spec(s, R, E),
        )
    _compile(fe.fleet_sweep_pallas, args, kw)


@pytest.mark.parametrize("slots", [64, 128])
def test_par_sweep_compiles_for_v5e(one_chip, slots):
    s = one_chip
    row, samp = _spec(s, R), _spec(s, R, K)
    kw = dict(
        t_end=row, skip=row, max_concurrency=slots, concurrency=C_PAR,
        slots=slots, block_k=BK, interpret=False,
    )
    _compile(fe.par_sweep_pallas, (row, samp, samp, samp), kw)



def test_draw_staging_compiles_for_v5e(one_chip):
    """The batched draw staging of the benchmark's sweep grid: 256
    exponential cells x 16 replicas x 15750 draws.  (The step-profile
    NHPP variant takes minutes to compile here, so the chip's own runs
    cover it.)"""
    from repro.core.simulator import stage_draw_cells

    D, reps, n = 256, 16, 15750
    params = {
        s: _spec(one_chip, D, 2, dtype=jnp.float64)
        for s in ("arrival", "warm", "cold")
    }
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    compiled = stage_draw_cells.lower(
        key, params, kinds=("exp", "exp", "exp"), replicas=reps, n=n
    ).compile()
    stacks = 3 * D * reps * n * 4  # three float32 [D·R, n] stacks
    assert compiled.memory_analysis().output_size_in_bytes >= stacks
