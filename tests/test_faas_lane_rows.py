"""The single-function Pallas kernel with replica rows on lanes
(DESIGN.md §5), interpreted on the CPU: bitwise against its jnp mirror
``kernels/ref.py`` at row counts that are not a multiple of the 128-row
block and at every slot width, in every variant a sweep launches; rows
stay independent across lanes; and the auto event chunk steps at most
2% more events than the stream asks for."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.execution import Execution
from repro.kernels import faas_event_step as fe
from repro.kernels import ref as kref

NEG = -1e30
K, BK = 240, 80  # three event chunks


def _base(R, M, seed, prestamped=False):
    """Empty pools and three [R, K] streams; per-row thresholds,
    horizons (some inside the stream, so padding-like events are
    stepped past ``t_end``) and warm-up cut-offs."""
    rng = np.random.default_rng(seed)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    gaps = rng.exponential(1 / rng.uniform(0.3, 2.0, (R, 1)), (R, K))
    dts = np.cumsum(gaps, axis=1) if prestamped else gaps
    state = (
        f32(np.zeros((R, M))),
        f32(np.full((R, M), NEG)),
        f32(np.full((R, M), NEG)),
        f32(np.zeros(R)),
        f32(rng.uniform(2.0, 40.0, R)),
    )
    streams = (f32(dts), f32(rng.exponential(2.0, (R, K))), f32(rng.exponential(2.3, (R, K))))
    kw = dict(
        t_end=f32(rng.uniform(40.0, 150.0, R)),
        skip=f32(rng.uniform(0.0, 10.0, R)),
        max_concurrency=max(2, M // 3),
        prestamped=prestamped,
    )
    return rng, state, streams, kw


def _variant(name, R, M, seed=0):
    """Positional args and kwargs of one kernel variant, for both twins."""
    prestamped = name in ("windows_prestamped", "retries")
    rng, state, streams, kw = _base(R, M, seed, prestamped)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    if name == "fused":
        keys = tuple(
            jnp.asarray(rng.integers(0, 2**32, (R, 2), dtype=np.uint64), jnp.uint32)
            for _ in range(3)
        )
        params = tuple(
            f32(np.stack([rng.uniform(0.3, 2.0, R), np.zeros(R)], axis=1))
            for _ in range(3)
        )
        kw.update(
            fused_dists=("exp", "exp", "exp"), fused_k=K, fused_keys=keys,
            fused_params=params, t_timeout=f32(rng.uniform(1.0, 5.0, R)),
            p_fail=f32(rng.uniform(0.0, 0.3, R)),
            fused_fail_keys=keys[0] ^ jnp.uint32(0x5BD1E995),
        )
        return state + (None, None, None), kw, dict(reliability=True)
    if name == "windows_prestamped":
        W = 5
        wb = np.sort(rng.uniform(0.0, 160.0, (R, W + 1)), axis=1)
        kw.update(window_bounds=f32(wb), n_windows=W)
    elif name == "grid_times":
        G = 6
        kw.update(grid_times=f32(np.sort(rng.uniform(0.0, 150.0, (R, G)), axis=1)), n_grid=G)
    elif name == "retries":
        first = (rng.uniform(size=(R, K)) < 0.7).astype(np.float64)
        child = np.arange(K)[None, :] + rng.integers(1, 20, (R, K))
        child = np.where(child < K, child, fe.NO_CHILD_F)
        kw.update(
            t_timeout=f32(rng.uniform(1.0, 5.0, R)),
            p_fail=f32(rng.uniform(0.0, 0.3, R)),
            fail_u=f32(rng.uniform(size=(R, K))),
            is_first=f32(first),
            child_pos=f32(child),
        )
        return state + streams, kw, dict(reliability=True, retries=True)
    elif name == "faults":
        E = 3
        kw.update(
            crash_rate=f32(rng.uniform(0.01, 0.1, R)),
            crash_u=f32(rng.uniform(size=(R, K))),
            cap_edges=f32(np.sort(rng.uniform(10.0, 120.0, (R, E)), axis=1)),
            cap_values=f32(rng.integers(1, 6, (R, E + 1))),
        )
    else:
        assert name == "staged", name
    return state + streams, kw, {}


def _assert_twins(args, kw, flags):
    out_k = fe.faas_sweep_pallas(*args, block_k=BK, interpret=True, **kw, **flags)
    out_r = kref.faas_sweep_ref(*args, **kw)
    for name, a, b in zip(("alive", "creation", "busy", "t", "acc"), out_k, out_r):
        assert a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    return out_k


@pytest.mark.parametrize("slots", [32, 64, 128])
@pytest.mark.parametrize("rows", [8, 136, 300])
def test_kernel_equals_ref_bitwise(rows, slots):
    args, kw, flags = _variant("staged", rows, slots, seed=rows + slots)
    _assert_twins(args, kw, flags)


VARIANTS = ("staged", "fused", "windows_prestamped", "grid_times", "retries", "faults")


@pytest.mark.parametrize("variant", VARIANTS)
def test_every_variant_equals_ref_bitwise(variant):
    args, kw, flags = _variant(variant, 136, 32, seed=7)
    acc = _assert_twins(args, kw, flags)[4]
    # the variant did work: arrivals were served, its columns were filled
    assert float(acc[:, 0].sum() + acc[:, 1].sum()) > 0
    assert float(acc[:, fe.ACC_COLS :].sum()) > 0 or variant in ("staged", "fused")


def test_rows_stay_independent_across_lanes():
    """Rows are replicas: permuting the input rows permutes the output
    rows bit for bit, whichever lane block and lane a row lands in."""
    args, kw, _ = _variant("faults", 136, 32, seed=3)
    perm = np.random.default_rng(5).permutation(136)
    take = lambda x: x if x is None or np.ndim(x) == 0 else x[perm]  # noqa: E731
    out = fe.faas_sweep_pallas(*args, block_k=BK, interpret=True, **kw)
    kw_p = {k: (take(v) if hasattr(v, "shape") else v) for k, v in kw.items()}
    out_p = fe.faas_sweep_pallas(
        *(take(a) for a in args), block_k=BK, interpret=True, **kw_p
    )
    for a, b in zip(out, out_p):
        np.testing.assert_array_equal(np.asarray(a)[perm], np.asarray(b))


@pytest.mark.parametrize("n_steps", [1, 680, 681, 15750, 100_000])
def test_auto_chunks_step_at_most_two_percent_padding(n_steps):
    """The auto chunk: whole sublane tiles under the VMEM ceiling,
    balanced over the stream, so the stepped events exceed ``n_steps``
    by at most 8 a chunk — under 1.02× for the benchmark's 15750 (where
    whole 128-lane chunks at the 10880 ceiling stepped 21760)."""
    bk = Execution().resolved_block_k(n_steps)
    lay = fe.sweep_layout(4096, n_steps, bk)
    assert lay["block_k"] == bk and bk % 8 == 0 and bk <= 680
    chunks = lay["stepped"] // bk
    assert n_steps <= lay["stepped"] < n_steps + 8 * chunks
    if n_steps == 15750:
        assert lay["stepped"] <= 1.02 * n_steps
        assert (lay["row_block"], lay["rows"], lay["stepped"]) == (128, 4096, 15936)


@pytest.mark.parametrize("rows,block,padded", [(1, 8, 8), (24, 24, 24), (127, 128, 128), (136, 128, 256), (4096, 128, 4096)])
def test_row_block_is_one_lane_tile_or_the_whole_axis(rows, block, padded):
    lay = fe.sweep_layout(rows, 100, 104)
    assert (lay["row_block"], lay["rows"]) == (block, padded)
