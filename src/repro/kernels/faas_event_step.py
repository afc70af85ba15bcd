"""Pallas TPU kernels for the SimFaaS hot loop: blocks of arrivals applied
to blocks of Monte-Carlo replicas with the instance pool resident in VMEM.

This is the paper's event-processing loop adapted to the TPU memory
hierarchy: instead of a per-event HBM round-trip of the pool state (the
``lax.scan`` formulation's behaviour on TPU), each kernel instance keeps its
block of pools in VMEM and sequentially applies ``K_blk`` arrivals per grid
step — HBM traffic collapses to (samples in + final state and accumulators
out), i.e. ``O(R·K)`` instead of ``O(R·K·M)``.

Grid layout (DESIGN.md §5): ``(R // block_r, K // block_k)`` with the
arrival-chunk axis innermost.  The state/accumulator output blocks are
indexed by the replica axis only, so they stay pinned in VMEM while the
``k`` axis advances — the standard TPU revisited-output accumulation
pattern — and are initialised from the input state at ``k == 0`` via
``pl.when``.  The single-function kernel lays replica rows on the 128
lanes (pools ``[M, 128]``, samples ``[block_k, 128]``, events on
sublanes); the fleet and par kernels keep ``BLOCK_R`` rows on sublanes
with slots and events on lanes.

Precision domain: the kernel state is f32 (TPU has no f64 VPU), so it is
the *throughput* engine for many-replica/many-cell what-if sweeps over
horizons where f32 clocks are exact enough.  The f64 ``lax.scan`` simulator
in ``repro.core`` remains the exactness path; ``kernels/ref.py`` mirrors
these kernels in pure f32 jnp (same arithmetic order, same tie-breaks) so
the two are bit-comparable; the kernels run interpreted only on the CPU.

Semantics per arrival (identical to ``core.simulator`` including the
measurement window): integrate running/idle instance-time over the window
clipped to ``[skip, t_end]`` → expire idle instances past the (per-row)
threshold → route to the newest idle instance (warm) → else create (cold)
→ else reject; arrivals past ``t_end`` are inert and request counters only
engage after ``skip`` (warm-up exclusion).  ``t_exp``, ``t_end`` and
``skip`` are all per-row traced inputs, so threshold/rate/horizon product
grids share one compile.

Windowed metrics (DESIGN.md §10): the metric-window *boundaries* are a
traced ``[R, W+1]`` input (only the window count ``W`` is static), so
irregular window grids and boundary-value sweeps share one compile; per
window the kernel accumulates cold/served/arrival counts by half-open
``[b_w, b_{w+1})`` membership plus exact ∫running / ∫idle instance-time
integrals (windows ignore ``skip`` — the grid is the caller's own
measurement request).  Transient curves (the temporal engine): a traced
``[R, G]`` grid of query times accumulates running/idle instance counts
and the no-idle-instance (cold-availability) indicator at each point —
each grid point falls in exactly one inter-arrival interval, so plain
additive accumulation reproduces the scan engine's point snapshots.

The par platform (``finish[M, c]`` per-request-slot state) has its own
kernel at the bottom of this module; see ``_par_kernel``.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.execution import (
    LANE,
    SUBLANE,
    lane_chunk,
    register_backend,
    sublane_chunk,
)
from repro.core import drawplan as dp

NEG = -1e30

# replica-block rows of the lane-major fleet and par kernels (rows are
# padded to a multiple of this before the kernel grid is formed); a
# fleet's functions are the rows of one block
BLOCK_R = 8

# Trace counter (kernel-local to avoid importing repro.core at call time):
# incremented when faas_sweep_pallas / par_sweep_pallas is (re-)traced.
# Tests pin that a horizon sweep with per-row t_end/skip costs one trace,
# not one per cell.
TRACE_COUNTS: collections.Counter = collections.Counter()

# acc columns: cold, warm, reject, t_run, t_idle, resp_cold, resp_warm, overflow
ACC_COLS = 8
# windowed columns per window: cold, served, arrivals, ∫running, ∫idle
WINDOW_COLS = 5
# transient-curve columns per grid point: running, idle, no_idle indicator
GRID_COLS = 3
# reliability columns (DESIGN.md §11): timeout, fail, retry, abandon —
# appended at the very END of the accumulator (after window and grid
# columns) so every pre-existing column offset is unchanged
RELY_COLS = 4
# platform-fault columns (DESIGN.md §15): crashes, evictions, interrupted
# attempts — appended after the reliability columns, same append-only rule
FAULT_COLS = 3
# par acc columns: ACC_COLS + ∫in-flight-requests
PAR_ACC_COLS = ACC_COLS + 1

# fleet acc columns (DESIGN.md §13): the single-function layout 0..7
# (cold, warm, reject, t_run, t_idle, resp_cold, resp_warm, overflow)
# followed by arrivals, enqueued, queue_served, queue_wait_sum, and the
# shared-capacity column — a cross-row MAX accumulator of cluster
# occupancy (all rows of a block carry the block's peak)
FLEET_ACC_COLS = 13

# child_pos sentinel for a last attempt (mirrors core.reliability.NO_CHILD):
# a power of two exactly representable in f32, larger than any padded
# stream width, so the one-hot activation scatter never matches it
NO_CHILD_F = float(1 << 30)


def _iota(shape, dim):
    """f32 index plane along ``dim`` (Mosaic builds integer iotas only)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)


def _event_col(ref, i):
    """Column ``i`` of a ``[Rb, Kb]`` per-event block as an ``[Rb]`` vector.

    A single dynamic lane does not lower on TPU, so this loads the aligned
    128-lane tile holding event ``i`` and picks its lane with a masked max:
    one vreg per event however wide the block, and exact for every value.
    Blocks are whole lane tiles, or one partial tile (``Kb <= LANE``).
    """
    if ref.shape[1] <= LANE:
        tile, lane = ref[...], i
    else:
        # lax.div/rem: i >= 0, and jnp's floor forms lower through sign
        base = pl.multiple_of(jax.lax.div(i, LANE) * LANE, LANE)
        tile, lane = ref[:, pl.ds(base, LANE)], jax.lax.rem(i, LANE)
    ids = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.max(jnp.where(ids == lane, tile, -jnp.inf), axis=1)


def _interpret() -> bool:
    """Pallas compiles on a TPU and runs interpreted on the CPU, the test
    platform; any other platform is refused rather than interpreted."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the pallas backend runs on a TPU (or interpreted on the CPU "
            f"for tests), not on platform {platform!r}; use backend='scan'"
        )
    return platform == "cpu"


def _pallas_call_32(kernel, inputs, **kw):
    """``pl.pallas_call`` with the kernel traced in 32-bit mode: the
    package runs with x64 on, and Mosaic has no 64-bit vector types (loop
    indices, sums of masks and scalar constants would otherwise widen)."""
    with jax.enable_x64(False):
        return pl.pallas_call(kernel, **kw)(*inputs)


def row_block(rows: int) -> int:
    """Replica rows per block of the single-function kernel: one
    :data:`LANE`-wide tile of rows, or — for fewer rows than that — the
    whole row axis padded to :data:`SUBLANE` (DESIGN.md §5)."""
    return min(LANE, -(-max(int(rows), 1) // SUBLANE) * SUBLANE)


def sweep_layout(rows: int, n_steps: int, block_k: int) -> dict:
    """How :func:`faas_sweep_pallas` blocks a ``rows × n_steps`` launch:
    the replica block, the event chunk, and the padded row and event
    counts it steps (``stepped`` events per row, of ``events`` asked)."""
    rb = row_block(rows)
    bk = sublane_chunk(block_k, n_steps)
    return dict(
        row_block=rb,
        rows=-(-rows // rb) * rb,
        block_k=bk,
        events=int(n_steps),
        stepped=-(-int(n_steps) // bk) * bk,
    )


def _faas_kernel(
    *refs,
    max_concurrency: int,
    n_steps: int,
    prestamped: bool,
    n_windows: int,
    n_grid: int,
    reliability: bool = False,
    retries: bool = False,
    fused_dists=None,
    crashes: bool = False,
    cap_steps: int = 0,
):
    # Replica rows ride the lanes (DESIGN.md §5).  Inputs (VMEM blocks):
    # state [M, Rb] ×3, per-row scalars [1, Rb] ×4 (+2 reliability),
    # optional window bounds [W+1, Rb] and curve grid [G, Rb], then either
    # samples [Kb, Rb] ×3 (+1 failure uniform, +2 retry streams) — events
    # on sublanes — or, fused draws (DESIGN.md §12), per-row uint32 key
    # pairs [2, Rb] ×3 and f32 dist params [2, Rb] ×3 (+1 failure key
    # pair).  Outputs are revisited across the k grid axis; with retries a
    # [Kp, Rb] activation scratch rides last.
    fused = fused_dists is not None
    (alive_in, creation_in, busy_in, t0_ref, texp_ref, tend_ref, skip_ref) = refs[:7]
    i = 7
    wb_ref = None
    grid_ref = None
    if n_windows:
        wb_ref = refs[i]
        i += 1
    if n_grid:
        grid_ref = refs[i]
        i += 1
    tto_ref = pf_ref = None
    if reliability:
        tto_ref, pf_ref = refs[i : i + 2]
        i += 2
    crate_ref = cape_ref = capv_ref = None
    if crashes:
        crate_ref = refs[i]
        i += 1
    if cap_steps:
        cape_ref, capv_ref = refs[i : i + 2]
        i += 2
    dt_ref = warm_ref = cold_ref = None
    akey_ref = wkey_ref = ckey_ref = fkey_ref = None
    apar_ref = wpar_ref = cpar_ref = None
    if fused:
        akey_ref, wkey_ref, ckey_ref = refs[i : i + 3]
        apar_ref, wpar_ref, cpar_ref = refs[i + 3 : i + 6]
        i += 6
    else:
        dt_ref, warm_ref, cold_ref = refs[i : i + 3]
        i += 3
    fail_ref = first_ref = child_ref = None
    if reliability:
        if fused:
            fkey_ref = refs[i]
        else:
            fail_ref = refs[i]
        i += 1
    crashu_ref = None
    if crashes:
        crashu_ref = refs[i]
        i += 1
    if retries:
        first_ref, child_ref = refs[i : i + 2]
        i += 2
    act_ref = doom_out = None
    outs = refs[i:]
    if retries:
        *outs, act_ref = outs  # the activation scratch rides last
    if crashes:
        *outs, doom_out = outs  # then the doom plane
    alive_out, creation_out, busy_out, t_out, acc_out = outs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        alive_out[...] = alive_in[...]
        creation_out[...] = creation_in[...]
        busy_out[...] = busy_in[...]
        t_out[...] = t0_ref[...]
        acc_out[...] = jnp.zeros(acc_out.shape, acc_out.dtype)
        if retries:
            act_ref[...] = jnp.zeros(act_ref.shape, act_ref.dtype)
        if crashes:
            # fresh pools carry no crash clock; cold starts stamp one
            doom_out[...] = jnp.full(doom_out.shape, jnp.inf, doom_out.dtype)

    alive = alive_out[...]
    creation = creation_out[...]
    busy = busy_out[...]
    t = t_out[...]  # [1, Rb]
    acc0 = acc_out[...]
    t_exp = texp_ref[...]  # [1, Rb]
    t_end = tend_ref[...]
    skip = skip_ref[...]
    t_to = tto_ref[...] if reliability else None
    p_fail = pf_ref[...] if reliability else None
    crate = crate_ref[...] if crashes else None
    # cap_e carries a leading 0.0 edge so the segment lookup is a plain
    # count (launcher prepends it); cap_v is the per-segment ceiling
    cap_e = cape_ref[...] if cap_steps else None  # [cap_steps, Rb]
    cap_v = capv_ref[...] if cap_steps else None
    w_lo = wb_ref[:-1, :] if n_windows else None  # [W, Rb]
    w_hi = wb_ref[1:, :] if n_windows else None
    g_times = grid_ref[...] if n_grid else None  # [G, Rb]
    slot_iota = _iota(alive.shape, 0)
    # every per-row quantity is a [1, Rb] lane row; a slot reduction is
    # elementwise across the M/8 vregs, then one sublane reduce
    slot_sum = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    if fused:
        # per-row stream keys/params live in VMEM once per chunk; draws are
        # regenerated per event from the global counter — no [Kb, Rb]
        # sample blocks exist anywhere (DESIGN.md §12)
        a_keys = akey_ref[...]  # uint32 [2, Rb]
        w_keys = wkey_ref[...]
        c_keys = ckey_ref[...]
        a_par = apar_ref[...]  # f32 [2, Rb]
        w_par = wpar_ref[...]
        c_par = cpar_ref[...]
        f_keys = fkey_ref[...] if reliability else None
        # global event counter base: chunk index × chunk length, the same
        # global-position arithmetic the retries activation plane uses
        gk0 = (pl.program_id(1) * n_steps).astype(jnp.uint32)
    if retries:
        # the activation plane spans the whole padded stream (event
        # positions are global across k chunks); row p of the scratch is
        # event p, read once per event and scattered into by 8-row tiles
        k0 = pl.program_id(1) * n_steps
        sub_iota = _iota((SUBLANE, alive.shape[1]), 0)
        n_tiles = act_ref.shape[0] // SUBLANE
    if crashes:
        doom0 = doom_out[...]

    def step(i, carry):
        alive, creation, busy, t, acc = carry[:5]
        doom = carry[5] if crashes else None
        if fused:
            gk = gk0 + i.astype(jnp.uint32)
            a_u0, a_u1 = dp.event_uniforms(a_keys[0:1], a_keys[1:2], gk)
            w_u0, w_u1 = dp.event_uniforms(w_keys[0:1], w_keys[1:2], gk)
            c_u0, c_u1 = dp.event_uniforms(c_keys[0:1], c_keys[1:2], gk)
            dt = dp.sample_dist(fused_dists[0], a_u0, a_u1, a_par[0:1], a_par[1:2])
            warm_s = dp.sample_dist(fused_dists[1], w_u0, w_u1, w_par[0:1], w_par[1:2])
            cold_s = dp.sample_dist(fused_dists[2], c_u0, c_u1, c_par[0:1], c_par[1:2])
            if reliability:
                fail_i, _ = dp.event_uniforms(f_keys[0:1], f_keys[1:2], gk)
        else:
            dt = dt_ref[pl.ds(i, 1), :]  # [1, Rb]
            warm_s = warm_ref[pl.ds(i, 1), :]
            cold_s = cold_ref[pl.ds(i, 1), :]
            if reliability:
                fail_i = fail_ref[pl.ds(i, 1), :]
        # prestamped: the sample slot carries the absolute arrival time
        # (non-stationary/trace streams); PAD_TIME entries are inert.
        t_new = dt if prestamped else t + dt

        # exact integrals over the measurement window (lo, hi]
        lo = jnp.clip(t, skip, t_end)
        hi = jnp.clip(t_new, skip, t_end)
        expire = busy + t_exp
        if crashes:
            # a crashed instance stops accruing run/idle time at its doom
            stop = jnp.minimum(hi, doom)
            run_t = jnp.clip(jnp.minimum(busy, stop) - lo, 0.0, None)
            idle_t = jnp.clip(
                jnp.minimum(expire, stop) - jnp.maximum(busy, lo), 0.0, None
            )
        else:
            run_t = jnp.clip(jnp.minimum(busy, hi) - lo, 0.0, None)
            idle_t = jnp.clip(
                jnp.minimum(expire, hi) - jnp.maximum(busy, lo), 0.0, None
            )
        run_sum = slot_sum(run_t * alive)
        idle_sum = slot_sum(idle_t * alive)

        if n_windows:
            # per-window exact integrals over (lo_e, hi_e] ∩ window — the
            # interval clipped to the horizon but NOT to skip (windows are
            # the caller's own measurement grid, DESIGN.md §7)
            lo_e = jnp.minimum(t, t_end)
            hi_e = jnp.minimum(t_new, t_end)
            wlo = jnp.maximum(w_lo, lo_e)[:, None, :]  # [W, 1, Rb]
            whi = jnp.minimum(w_hi, hi_e)[:, None, :]
            run_w = jnp.clip(jnp.minimum(busy[None], whi) - wlo, 0.0, None)
            idle_w = jnp.clip(
                jnp.minimum(expire[None], whi) - jnp.maximum(busy[None], wlo),
                0.0,
                None,
            )  # [W, M, Rb]
            w_run = (run_w * alive[None]).sum(axis=1)  # [W, Rb]
            w_idle = (idle_w * alive[None]).sum(axis=1)

        if n_grid:
            # point snapshots at grid times inside (t, min(t_new, t_end)]:
            # instance counts from the pre-expiration state, exactly as the
            # temporal scan engine samples them
            in_win = (g_times > t) & (g_times <= jnp.minimum(t_new, t_end))  # [G, Rb]
            g3 = g_times[:, None, :]
            live_g = (alive[None] > 0) & (expire[None] > g3)  # [G, M, Rb]
            running_g = (live_g & (busy[None] > g3)).sum(axis=1)
            idle_g = (live_g & (busy[None] <= g3)).sum(axis=1)
            g_run = jnp.where(in_win, running_g.astype(jnp.float32), 0.0)
            g_idle = jnp.where(in_win, idle_g.astype(jnp.float32), 0.0)
            g_cold = (in_win & (idle_g == 0)).astype(jnp.float32)

        # expirations (and crash exits: whichever clock fires first)
        exit_time = jnp.minimum(expire, doom) if crashes else expire
        expired = (alive > 0) & (exit_time <= t_new)
        if crashes:
            # a crash only counts when the doom instant itself is inside
            # the measured window — pad events past t_end stay inert
            crash_ok = (
                expired & (doom < expire) & (doom > skip) & (doom <= t_end)
            )
            n_crash = slot_sum(crash_ok.astype(jnp.float32))
        alive = jnp.where(expired, 0.0, alive)

        if cap_steps:
            # capacity churn: ceiling in effect at this arrival, then
            # evict the newest idle instances above it (DESIGN.md §15);
            # cap_e's leading 0-edge makes the segment index a plain count
            seg = slot_sum((cap_e <= t_new).astype(jnp.float32)) - 1.0
            cap_col = _iota(cap_v.shape, 0)
            cap_now = slot_sum(cap_v * (cap_col == seg))  # [1, Rb]
            idle_now = (alive > 0) & (busy <= t_new)
            over = slot_sum(alive) - cap_now
            # slot a on axis 0, slot b on axis 1: [M, M, Rb]
            cre_a = creation[:, None, :]
            cre_b = creation[None, :, :]
            ia = slot_iota[:, None, :]
            ib = slot_iota[None, :, :]
            newer = (cre_b > cre_a) | ((cre_b == cre_a) & (ib < ia))
            rank = (
                (idle_now[None, :, :] & newer).astype(jnp.float32).sum(axis=1)
            )  # [M, Rb] idle instances strictly newer than each slot
            evict = idle_now & (rank < over) & (t_new <= t_end)
            n_evict = slot_sum((evict & (t_new > skip)).astype(jnp.float32))
            alive = jnp.where(evict, 0.0, alive)

        # routing: newest idle instance
        idle = (alive > 0) & (busy <= t_new)
        best = jnp.max(jnp.where(idle, creation, NEG), axis=0, keepdims=True)
        any_idle = best > NEG * 0.5
        # first slot achieving the max (tie-break by slot index, as the ref)
        is_best = idle & (creation >= best) & any_idle
        first_best = jnp.min(
            jnp.where(is_best, slot_iota, 1e9), axis=0, keepdims=True
        )

        free = alive <= 0
        first_free = jnp.min(jnp.where(free, slot_iota, 1e9), axis=0, keepdims=True)
        # not free.any(): Mosaic lowers bool reductions through a float
        # proxy that widens to f64 when the caller runs with x64 on
        any_free = first_free < 1e9
        n_alive = slot_sum(alive)

        active = t_new <= t_end
        if retries:
            # Non-first attempts stay inert until their parent's failure /
            # timeout / rejection switched them on (inactive events still
            # advance the clock, integrate and expire — no-op arrivals).
            is_first = first_ref[pl.ds(i, 1), :]
            child = child_ref[pl.ds(i, 1), :]
            act_i = act_ref[pl.ds(k0 + i, 1), :]
            active = active & ((is_first > 0) | (act_i > 0))
        counted = t_new > skip
        can_cold = (~any_idle) & (n_alive < max_concurrency) & any_free
        if cap_steps:
            # admission gate while degraded: no cold start over the ceiling
            can_cold = can_cold & (n_alive < cap_now)
        overflow = (~any_idle) & (n_alive < max_concurrency) & (~any_free) & active
        is_warm = any_idle & active
        is_cold = can_cold & active
        is_reject = (~any_idle) & (~can_cold) & active

        chosen = jnp.where(is_warm, first_best, first_free)  # f32 slot id
        service = jnp.where(is_warm, warm_s, cold_s)
        if reliability:
            # instance freed at min(departure, t_arrival + t_timeout); the
            # 1e30 sentinel makes min() the identity when timeouts are off
            occupancy = jnp.minimum(service, t_to)
        else:
            occupancy = service
        assign = is_warm | is_cold
        sel = (slot_iota == chosen) & assign
        busy = jnp.where(sel, t_new + occupancy, busy)
        creation = jnp.where(sel & is_cold, t_new, creation)
        alive = jnp.where(sel & is_cold, 1.0, alive)
        if crashes:
            # Exp(crash_rate) lifetime stamped at cold start (memoryless ⇒
            # hazard-equivalent); warm hits keep the instance's old doom
            crash_i = crashu_ref[pl.ds(i, 1), :]
            life = -jnp.log(1.0 - crash_i) / crate
            doom = jnp.where(sel & is_cold, t_new + life, doom)
            doom_chosen = jnp.min(
                jnp.where(sel, doom, jnp.inf), axis=0, keepdims=True
            )

        cc = counted
        if reliability:
            timed_out = assign & (service > t_to)
            failed = assign & ~timed_out & (fail_i < p_fail)
            if crashes:
                interrupted = (
                    assign
                    & ~timed_out
                    & ~failed
                    & (doom_chosen < t_new + occupancy)
                )
                trigger = timed_out | failed | interrupted | is_reject
            else:
                trigger = timed_out | failed | is_reject
            cold_resp = jnp.minimum(cold_s, t_to)
            warm_resp = jnp.minimum(warm_s, t_to)
        else:
            if crashes:
                interrupted = assign & (doom_chosen < t_new + occupancy)
            cold_resp, warm_resp = cold_s, warm_s
        f32 = lambda b: b.astype(jnp.float32)  # noqa: E731
        # the delta is a sublane stack of [1, Rb] lane rows
        rows = [
            f32(is_cold & cc),
            f32(is_warm & cc),
            f32(is_reject & cc),
            run_sum,
            idle_sum,
            jnp.where(is_cold & cc, cold_resp, 0.0),
            jnp.where(is_warm & cc, warm_resp, 0.0),
            f32(overflow),
        ]
        if n_windows:
            # half-open window membership [b_w, b_{w+1}) of the arrival
            # instant (windows ignore skip — the grid is the caller's own
            # measurement request)
            onehot = (t_new >= w_lo) & (t_new < w_hi) & active  # [W, Rb]
            rows += [
                f32(onehot & is_cold),
                f32(onehot & (is_cold | is_warm)),
                f32(onehot),  # includes rejects
                w_run,
                w_idle,
            ]
        if n_grid:
            rows += [g_run, g_idle, g_cold]
        if reliability:
            if retries:
                has_child = child < NO_CHILD_F
                r_retry = (is_first <= 0) & active & cc
                r_abandon = trigger & ~has_child & cc
                # re-enqueue: switch on the successor's row of the plane,
                # one 8-event tile at a time (NO_CHILD matches no row, so
                # last attempts drop out)
                on = jnp.where(trigger, child, -1.0)

                def scatter(j, _):
                    base = pl.multiple_of(j * SUBLANE, SUBLANE)
                    pos = sub_iota + base.astype(jnp.float32)
                    tile = act_ref[pl.ds(base, SUBLANE), :]
                    act_ref[pl.ds(base, SUBLANE), :] = jnp.where(
                        pos == on, 1.0, tile
                    )
                    return 0

                jax.lax.fori_loop(0, n_tiles, scatter, 0)
            else:
                r_retry = jnp.zeros_like(trigger)
                r_abandon = trigger & cc
            rows += [
                f32(timed_out & cc),
                f32(failed & cc),
                f32(r_retry),
                f32(r_abandon),
            ]
        if crashes or cap_steps:
            zero = jnp.zeros_like(run_sum)
            rows += [
                n_crash if crashes else zero,
                n_evict if cap_steps else zero,
                f32(interrupted & cc) if crashes else zero,
            ]
        acc = acc + jnp.concatenate(rows, axis=0)
        out = (alive, creation, busy, t_new, acc)
        if crashes:
            out = out + (doom,)
        return out

    carry0 = (alive, creation, busy, t, acc0)
    if crashes:
        carry0 = carry0 + (doom0,)
    carry = jax.lax.fori_loop(0, n_steps, step, carry0)
    alive, creation, busy, t, acc = carry[:5]
    if crashes:
        doom_out[...] = carry[5]
    alive_out[...] = alive
    creation_out[...] = creation
    busy_out[...] = busy
    t_out[...] = t
    acc_out[...] = acc


# VMEM the compiler may give a kernel by default (v5e's scoped limit);
# a large explicit block_k, or a retry sweep's whole-stream activation
# scratch, can need more
_SCOPED_VMEM = 16 << 20


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_concurrency",
        "block_r",
        "block_k",
        "interpret",
        "prestamped",
        "n_windows",
        "n_grid",
        "reliability",
        "retries",
        "fused_dists",
        "fused_k",
    ),
)
def faas_sweep_pallas(
    alive,  # f32 [R, M] 0/1
    creation,  # f32 [R, M]
    busy,  # f32 [R, M]
    t0,  # f32 [R]
    t_exp,  # f32 [R]  per-row expiration threshold (sweep axis)
    dts,  # f32 [R, K]  inter-arrival gaps, or absolute times if prestamped
    warms,  # f32 [R, K]
    colds,  # f32 [R, K]
    *,
    t_end=float("inf"),  # f32 [R] or scalar — per-row horizon (sweep axis)
    skip=0.0,  # f32 [R] or scalar — per-row warm-up exclusion
    window_bounds=None,  # f32 [R, W+1] traced window boundaries (irregular OK)
    grid_times=None,  # f32 [R, G] traced transient-curve query times
    t_timeout=None,  # f32 [R] per-row execution timeout (reliability)
    p_fail=None,  # f32 [R] per-row failure probability (reliability)
    fail_u=None,  # f32 [R, K] per-event failure uniforms (reliability)
    is_first=None,  # f32 [R, K] 0/1 first-attempt flags (retries)
    child_pos=None,  # f32 [R, K] retry-successor positions (retries)
    fused_keys=None,  # uint32 [R, 2] ×3 (arrival, warm, cold) stream keys
    fused_params=None,  # f32 [R, 2] ×3 per-row (p0, p1) dist params
    fused_fail_keys=None,  # uint32 [R, 2] failure-stream keys (reliability)
    crash_rate=None,  # f32 [R] per-row crash hazard (faults, DESIGN.md §15)
    crash_u=None,  # f32 [R, K] per-event crash-lifetime uniforms (faults)
    cap_edges=None,  # f32 [R, E] capacity-profile step times (faults)
    cap_values=None,  # f32 [R, E+1] per-segment capacity ceilings (faults)
    max_concurrency: int,
    block_r=None,  # replica block; None derives it (:func:`row_block`)
    block_k: int = 512,
    interpret: bool = False,
    prestamped: bool = False,
    n_windows: int = 0,
    n_grid: int = 0,
    reliability: bool = False,
    retries: bool = False,
    fused_dists=None,  # static ("exp", ...) ×3 → in-VMEM draw generation
    fused_k: int = 0,  # static event count when fused (no dts)
):
    """Run the full event loop: K arrivals in ``block_k`` chunks, pool in VMEM.

    Returns ``(alive, creation, busy, t, acc)`` with
    ``acc[R, ACC_COLS + WINDOW_COLS*W + GRID_COLS*G]``.  Rows are
    independent (replica × grid-cell); ``t_exp``, ``t_end``, ``skip`` and
    the window boundaries all vary per row (traced inputs, NOT compile-time
    constants), so an entire (threshold × rate × horizon) product grid is
    one kernel launch and one compile — and with ``prestamped=True`` the
    rows carry absolute-timestamp streams, so a sweep over *rate profiles*
    (each row thinned from its own profile) is also one launch.

    The kernel runs with rows on lanes (DESIGN.md §5): inside this one
    program every ``[R, K]`` stream is padded — rows to the replica
    block, events to a ``block_k`` multiple, with inert fills — and
    transposed to ``[K, R]`` in the same pass, and the results are
    transposed back, so callers see ``[R, ...]`` throughout.

    ``n_windows > 0`` appends per-window metric columns over the traced
    (possibly irregular) boundary rows ``window_bounds``: cold
    ``[A, A+W)``, served ``[A+W, A+2W)``, arrivals incl. rejects
    ``[A+2W, A+3W)``, ∫running ``[A+3W, A+4W)``, ∫idle ``[A+4W, A+5W)``
    where ``A = ACC_COLS``.  ``n_grid > 0`` appends transient-curve
    columns at the traced query times ``grid_times``: running counts
    ``[B, B+G)``, idle counts ``[B+G, B+2G)``, no-idle indicator
    ``[B+2G, B+3G)`` where ``B = A + WINDOW_COLS*W``.
    """
    TRACE_COUNTS["faas_sweep_pallas"] += 1
    fused = fused_dists is not None
    if fused:
        assert not retries, "fused draws do not serve retry streams"
    # the fault flags are pytree-structural (None vs array), not extra
    # static args: crash_rate stays a traced row vector, so a crash-rate
    # sweep shares one trace
    crashes = crash_u is not None
    cap_steps = 0 if cap_values is None else cap_values.shape[1]
    if fused:
        assert not crashes and not cap_steps, (
            "fused draws do not serve platform faults"
        )
    R, M = alive.shape
    n = fused_k if fused else dts.shape[1]
    rb = row_block(R) if block_r is None else block_r
    Rp = -(-R // rb) * rb
    K = -(-n // block_k) * block_k
    # a chunk is whole sublane tiles, or the whole (single-chunk) stream
    assert block_k % SUBLANE == 0 or K == block_k, block_k
    assert rb % LANE == 0 or rb == Rp, (rb, Rp)

    def lanes(x, dtype=jnp.float32):
        """[R, X] → [X, Rp]: padded rows copy the last real one."""
        x = jnp.asarray(x, dtype)
        return jnp.pad(x, ((0, Rp - R), (0, 0)), mode="edge").T

    def per_row(x):
        """A per-row value (or scalar) → a [1, Rp] lane row."""
        return lanes(jnp.broadcast_to(jnp.asarray(x, jnp.float32), (R,))[:, None])

    def events(x, fill):
        """[R, n] → [K, Rp], events on sublanes.  Fills are inert: a 1e30
        gap or timestamp is past every ``t_end``, so padded events never
        engage, and the other fills only keep the arithmetic finite."""
        x = jnp.asarray(x, jnp.float32)
        return jnp.pad(
            x, ((0, Rp - R), (0, K - n)), constant_values=fill
        ).T

    grid = (Rp // rb, K // block_k)
    acc_cols = (
        ACC_COLS
        + WINDOW_COLS * n_windows
        + GRID_COLS * n_grid
        + (RELY_COLS if reliability else 0)
        + (FAULT_COLS if crashes or cap_steps else 0)
    )

    def rows_spec(n_rows):
        return pl.BlockSpec((n_rows, rb), lambda r, k: (0, r))

    state_spec = rows_spec(M)
    t_spec = rows_spec(1)
    samp_spec = pl.BlockSpec((block_k, rb), lambda r, k: (k, r))

    kernel = functools.partial(
        _faas_kernel,
        max_concurrency=max_concurrency,
        n_steps=block_k,
        prestamped=prestamped,
        n_windows=n_windows,
        n_grid=n_grid,
        reliability=reliability,
        retries=retries,
        fused_dists=fused_dists,
        crashes=crashes,
        cap_steps=cap_steps,
    )
    in_specs = [state_spec, state_spec, state_spec, t_spec, t_spec, t_spec, t_spec]
    inputs = [
        lanes(alive),
        lanes(creation),
        lanes(busy),
        per_row(t0),
        per_row(t_exp),
        per_row(t_end),
        per_row(skip),
    ]
    if n_windows:
        in_specs.append(rows_spec(n_windows + 1))
        inputs.append(lanes(window_bounds))
    if n_grid:
        in_specs.append(rows_spec(n_grid))
        inputs.append(lanes(grid_times))
    if reliability:
        in_specs += [t_spec, t_spec]
        inputs += [per_row(t_timeout), per_row(p_fail)]
    if crashes:
        in_specs.append(t_spec)
        inputs.append(per_row(crash_rate))
    if cap_steps:
        in_specs += [rows_spec(cap_steps), rows_spec(cap_steps)]
        # prepend the implicit t=0 edge so the in-kernel segment lookup is
        # a plain count (and the block is never zero-height for E == 0)
        inputs += [
            lanes(
                jnp.concatenate(
                    [
                        jnp.zeros((R, 1), jnp.float32),
                        jnp.asarray(cap_edges, jnp.float32),
                    ],
                    axis=1,
                )
            ),
            lanes(cap_values),
        ]
    if fused:
        # the entire per-row sample state: three 8-byte key pairs and three
        # (p0, p1) param pairs — no [K, R] buffers exist anywhere
        in_specs += [rows_spec(2)] * 6
        inputs += [lanes(k, jnp.uint32) for k in fused_keys]
        inputs += [lanes(p) for p in fused_params]
        if reliability:
            in_specs.append(rows_spec(2))
            inputs.append(lanes(fused_fail_keys, jnp.uint32))
    else:
        in_specs += [samp_spec, samp_spec, samp_spec]
        inputs += [events(dts, 1e30), events(warms, 1.0), events(colds, 1.0)]
        if reliability:
            # padded events are inert (active=False via the 1e30 clock):
            # fail_u=1.0 never fails (p_fail < 1)
            in_specs.append(samp_spec)
            inputs.append(events(fail_u, 1.0))
        if crashes:
            # any doom a padded event stamps is past t_end, never counted;
            # 0.0 keeps the log finite
            in_specs.append(samp_spec)
            inputs.append(events(crash_u, 0.0))
    if retries:
        # is_first=0 keeps padded events inactive, NO_CHILD never scatters
        in_specs += [samp_spec, samp_spec]
        inputs += [events(is_first, 0.0), events(child_pos, NO_CHILD_F)]
    out_specs = [state_spec, state_spec, state_spec, t_spec, rows_spec(acc_cols)]
    out_shape = [
        jax.ShapeDtypeStruct((M, Rp), jnp.float32),
        jax.ShapeDtypeStruct((M, Rp), jnp.float32),
        jax.ShapeDtypeStruct((M, Rp), jnp.float32),
        jax.ShapeDtypeStruct((1, Rp), jnp.float32),
        jax.ShapeDtypeStruct((acc_cols, Rp), jnp.float32),
    ]
    if crashes:
        # the per-slot doom plane persists across k chunks like the pool
        out_specs.append(state_spec)
        out_shape.append(jax.ShapeDtypeStruct((M, Rp), jnp.float32))
    kw = {}
    # VMEM of the streamed blocks: every [block_k, rb] sample block is
    # double-buffered; a large explicit block_k raises the limit with it
    vmem = 2 * sum(spec is samp_spec for spec in in_specs) * block_k * rb * 4
    if retries:
        # the activation plane spans the WHOLE padded stream (event
        # positions are global across k chunks): one scratch per replica
        # block, zeroed at its first chunk
        vmem += K * rb * 4
        kw["scratch_shapes"] = [pltpu.VMEM((K, rb), jnp.float32)]
    if vmem > _SCOPED_VMEM // 2:
        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem + _SCOPED_VMEM
        )
    out = _pallas_call_32(
        kernel,
        inputs,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **kw,
    )
    alive_n, creation_n, busy_n, t_n, acc = out[:5]
    return (
        alive_n.T[:R],
        creation_n.T[:R],
        busy_n.T[:R],
        t_n[0, :R],
        acc.T[:R],
    )


def _pad_rows(x, pad_c, fill=None):
    """Row-pad ``[C, ...]`` with copies of row 0 (or a constant fill)."""
    if not pad_c:
        return x
    if fill is None:
        return jnp.concatenate([x, jnp.broadcast_to(x[:1], (pad_c,) + x.shape[1:])])
    return jnp.concatenate(
        [x, jnp.full((pad_c,) + x.shape[1:], fill, x.dtype)]
    )


@register_backend(
    "pallas",
    precision="f32",
    kind="block",
    shardable=True,
    description="VMEM-resident f32 Pallas block kernel (interpreted on CPU)",
    engines=("scan", "temporal"),
)
def _pallas_sweep_rows(
    alive0, creation0, busy0, t0, t_exp, t_end, skip, dts, warms, colds,
    *, block_k, fused=None, **kw,
):
    """The sweep engine's ``pallas`` row launcher (``BackendSpec.launch``):
    run :func:`faas_sweep_pallas` on the ``[C, ...]`` rows at the chunk
    :func:`sweep_layout` gives, and return the ``[C, cols]``
    accumulator.  The kernel program pads and transposes the streams
    itself (``dts`` rows are gaps, or absolute times when
    ``kw['prestamped']`` — the 1e30 pad is inert as either).  Serves both
    the steady-state (scan) and transient (temporal, via ``grid_times``)
    engines — the pool-state family.

    With ``fused`` (a dict of ``dists``/``keys``/``params``/``fail_keys``/
    ``n_steps`` from the DrawPlan lowering, DESIGN.md §12) there are no
    sample buffers at all: only the [C, 2] key/param pairs go in, and the
    return value is ``(acc, t_final)`` so the caller can check stream
    coverage from the kernel's own clock.  Padded tail events past
    ``n_steps`` keep drawing from the counter but are inert once the clock
    clears ``t_end``.
    """
    C = alive0.shape[0]
    if fused is not None:
        n = int(fused["n_steps"])
        rely_kw = {}
        if kw.get("t_timeout") is not None:
            rely_kw = dict(
                reliability=True,
                fused_fail_keys=jnp.asarray(fused["fail_keys"], jnp.uint32),
            )
        out = faas_sweep_pallas(
            alive0, creation0, busy0, t0, t_exp, None, None, None,
            t_end=t_end,
            skip=skip,
            block_k=sweep_layout(C, n, block_k)["block_k"],
            interpret=_interpret(),
            fused_dists=tuple(fused["dists"]),
            fused_k=n,
            fused_keys=tuple(jnp.asarray(k, jnp.uint32) for k in fused["keys"]),
            fused_params=tuple(jnp.asarray(p, jnp.float32) for p in fused["params"]),
            **rely_kw,
            **kw,
        )
        return out[4], out[3]
    out = faas_sweep_pallas(
        alive0, creation0, busy0, t0, t_exp, dts, warms, colds,
        t_end=t_end,
        skip=skip,
        block_k=sweep_layout(C, dts.shape[1], block_k)["block_k"],
        interpret=_interpret(),
        reliability=kw.get("t_timeout") is not None,
        retries=kw.get("is_first") is not None,
        **kw,
    )
    return out[4]


def faas_block_step_pallas(
    alive,
    creation,
    busy,
    t0,
    dts,
    warms,
    colds,
    *,
    t_exp: float,
    max_concurrency: int,
    block_r=None,
    interpret: bool = False,
):
    """Legacy single-chunk entry point (scalar threshold, no window masking).

    Kept for the kernel test-suite and micro-benchmarks; the sweep engine
    uses :func:`faas_sweep_pallas`.  ``t_end=+inf`` / ``skip=0`` reduce the
    windowed kernel to the original unmasked arithmetic.
    """
    R = alive.shape[0]
    K = dts.shape[1]
    t_exp_rows = jnp.full((R,), t_exp, dtype=jnp.float32)
    return faas_sweep_pallas(
        alive,
        creation,
        busy,
        t0,
        t_exp_rows,
        dts,
        warms,
        colds,
        max_concurrency=max_concurrency,
        block_r=block_r,
        block_k=K,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Par platform kernel: per-instance concurrency value > 1 (finish[M, c])
# ---------------------------------------------------------------------------


def _par_kernel(
    alive_in,  # f32 [Rb, Mp] 0/1 (padded slots dead)
    creation_in,  # f32 [Rb, Mp]
    finish_in,  # f32 [Rb, c*Mp] — c lane-aligned planes of Mp slots
    t0_ref,  # f32 [Rb, 1]
    texp_ref,  # f32 [Rb, 1]
    tend_ref,  # f32 [Rb, 1]
    skip_ref,  # f32 [Rb, 1]
    dt_ref,  # f32 [Rb, Kb]
    warm_ref,  # f32 [Rb, Kb]
    cold_ref,  # f32 [Rb, Kb]
    alive_out,
    creation_out,
    finish_out,
    t_out,
    acc_out,  # f32 [Rb, PAR_ACC_COLS]
    *,
    max_concurrency: int,
    concurrency: int,
    slots: int,  # real slot count M (<= Mp; padded slots masked out)
    n_steps: int,
    prestamped: bool,
):
    """The par platform's event loop: ``finish`` holds per-request-slot
    finish times as ``c`` lane-aligned ``[Rb, Mp]`` planes concatenated
    along the column axis (plane ``j`` at columns ``[j*Mp, (j+1)*Mp)``) —
    the explicit VMEM layout for the ``finish[M, c]`` state.  Padded slots
    (``m >= slots``) are masked out of the free-slot search so they are
    never cold-started into."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        alive_out[...] = alive_in[...]
        creation_out[...] = creation_in[...]
        finish_out[...] = finish_in[...]
        t_out[...] = t0_ref[...]
        acc_out[...] = jnp.zeros(acc_out.shape, acc_out.dtype)

    alive = alive_out[...]
    creation = creation_out[...]
    finish2 = finish_out[...]  # [Rb, c*Mp]
    t = t_out[...][:, 0]
    acc0 = acc_out[...]
    t_exp = texp_ref[...][:, 0]
    t_end = tend_ref[...][:, 0]
    skip = skip_ref[...][:, 0]
    Rb, Mp = alive.shape
    c = concurrency
    slot_iota = _iota((Rb, Mp), 1)
    real = slot_iota < slots  # padded slots excluded from the pool
    sub_iota = _iota((Rb, c), 1)
    # the sub-slot index across the [Rb, c, Mp] planes (a [Rb, c] mask
    # cannot be broadcast along lanes in Mosaic)
    sub3_iota = _iota((Rb, c, Mp), 1)

    def step(i, carry):
        alive, creation, finish2, t, acc = carry
        finish = finish2.reshape(Rb, c, Mp)
        dt = _event_col(dt_ref, i)
        warm_s = _event_col(warm_ref, i)
        cold_s = _event_col(cold_ref, i)
        t_new = dt if prestamped else t + dt
        busy = finish.max(axis=1)  # [Rb, Mp]

        lo = jnp.clip(t, skip, t_end)
        hi = jnp.clip(t_new, skip, t_end)
        expire = busy + t_exp[:, None]
        run_t = jnp.clip(jnp.minimum(busy, hi[:, None]) - lo[:, None], 0.0, None)
        idle_t = jnp.clip(
            jnp.minimum(expire, hi[:, None]) - jnp.maximum(busy, lo[:, None]),
            0.0,
            None,
        )
        run_sum = (run_t * alive).sum(axis=1)
        idle_sum = (idle_t * alive).sum(axis=1)
        # request-level in-flight integral: every request slot of a live
        # instance contributes its overlap with the window
        flight_t = jnp.clip(
            jnp.minimum(finish, hi[:, None, None]) - lo[:, None, None], 0.0, None
        )
        # lane sums, then the c planes (Mosaic reduces one trailing axis)
        flight_sum = (flight_t * alive[:, None, :]).sum(axis=2).sum(axis=1)

        expired = (alive > 0) & (expire <= t_new[:, None])
        alive = jnp.where(expired, 0.0, alive)

        # routing: newest instance with spare request capacity
        in_flight = (finish > t_new[:, None, None]).sum(axis=1)  # [Rb, Mp]
        has_cap = (alive > 0) & (in_flight < c)
        best = jnp.max(jnp.where(has_cap, creation, NEG), axis=1)
        any_cap = best > NEG * 0.5
        is_best = has_cap & (creation >= best[:, None]) & any_cap[:, None]
        first_best = jnp.min(jnp.where(is_best, slot_iota, 1e9), axis=1)

        free = (alive <= 0) & real
        first_free = jnp.min(jnp.where(free, slot_iota, 1e9), axis=1)
        any_free = first_free < 1e9
        n_alive = alive.sum(axis=1)

        active = t_new <= t_end
        counted = t_new > skip
        can_cold = (~any_cap) & (n_alive < max_concurrency) & any_free
        overflow = (~any_cap) & (n_alive < max_concurrency) & (~any_free) & active
        is_warm = any_cap & active
        is_cold = can_cold & active
        is_reject = (~any_cap) & (~can_cold) & active

        chosen = jnp.where(is_warm, first_best, first_free)
        service = jnp.where(is_warm, warm_s, cold_s)
        assign = is_warm | is_cold
        sel = (slot_iota == chosen[:, None]) & assign[:, None]  # [Rb, Mp]
        # first free request sub-slot on the chosen instance (pre-wipe
        # finishes, as the scan: a cold-started instance has every finish
        # stale <= t_new, so its sub-slot is 0)
        chosen_fin = jnp.where(sel[:, None, :], finish, 0.0).sum(axis=2)  # [Rb, c]
        sub_free = chosen_fin <= t_new[:, None]
        first_sub = jnp.min(jnp.where(sub_free, sub_iota, 1e9), axis=1)  # [Rb]
        # a cold start repurposes a (possibly stale) slot: wipe it first
        wipe = sel & is_cold[:, None]
        finish = jnp.where(wipe[:, None, :], NEG, finish)
        set3 = sel[:, None, :] & (sub3_iota == first_sub[:, None, None])
        finish = jnp.where(set3, (t_new + service)[:, None, None], finish)
        creation = jnp.where(sel & is_cold[:, None], t_new[:, None], creation)
        alive = jnp.where(sel & is_cold[:, None], 1.0, alive)

        cc = counted
        delta = jnp.stack(
            [
                (is_cold & cc).astype(jnp.float32),
                (is_warm & cc).astype(jnp.float32),
                (is_reject & cc).astype(jnp.float32),
                run_sum,
                idle_sum,
                jnp.where(is_cold & cc, cold_s, 0.0),
                jnp.where(is_warm & cc, warm_s, 0.0),
                overflow.astype(jnp.float32),
                flight_sum,
            ],
            axis=1,
        )
        return alive, creation, finish.reshape(Rb, c * Mp), t_new, acc + delta

    alive, creation, finish2, t, acc = jax.lax.fori_loop(
        0, n_steps, step, (alive, creation, finish2, t, acc0)
    )
    alive_out[...] = alive
    creation_out[...] = creation
    finish_out[...] = finish2
    t_out[...] = t[:, None]
    acc_out[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_concurrency",
        "concurrency",
        "slots",
        "block_r",
        "block_k",
        "interpret",
        "prestamped",
    ),
)
def par_sweep_pallas(
    t_exp,  # f32 [R]
    dts,  # f32 [R, K]
    warms,
    colds,
    *,
    t_end,  # f32 [R] or scalar
    skip,  # f32 [R] or scalar
    max_concurrency: int,
    concurrency: int,
    slots: int,
    block_r: int = 8,
    block_k: int = 512,
    interpret: bool = False,
    prestamped: bool = False,
):
    """Par-platform block sweep from an empty pool.  The slot axis is
    padded to a :data:`LANE` multiple so each of the ``concurrency``
    ``finish`` planes is lane-aligned; returns ``acc[R, PAR_ACC_COLS]``."""
    TRACE_COUNTS["par_sweep_pallas"] += 1
    R = dts.shape[0]
    K = dts.shape[1]
    assert R % block_r == 0, (R, block_r)
    assert K % block_k == 0, (K, block_k)
    assert block_k <= LANE or block_k % LANE == 0, block_k  # _event_col tiles
    Mp = -(-slots // LANE) * LANE
    c = concurrency
    t_end = jnp.broadcast_to(jnp.asarray(t_end, jnp.float32), (R,))
    skip = jnp.broadcast_to(jnp.asarray(skip, jnp.float32), (R,))
    alive0 = jnp.zeros((R, Mp), jnp.float32)
    creation0 = jnp.full((R, Mp), NEG, jnp.float32)
    finish0 = jnp.full((R, c * Mp), NEG, jnp.float32)
    t0 = jnp.zeros((R,), jnp.float32)
    grid = (R // block_r, K // block_k)

    state_spec = pl.BlockSpec((block_r, Mp), lambda r, k: (r, 0))
    fin_spec = pl.BlockSpec((block_r, c * Mp), lambda r, k: (r, 0))
    samp_spec = pl.BlockSpec((block_r, block_k), lambda r, k: (r, k))
    t_spec = pl.BlockSpec((block_r, 1), lambda r, k: (r, 0))
    acc_spec = pl.BlockSpec((block_r, PAR_ACC_COLS), lambda r, k: (r, 0))

    kernel = functools.partial(
        _par_kernel,
        max_concurrency=max_concurrency,
        concurrency=c,
        slots=slots,
        n_steps=block_k,
        prestamped=prestamped,
    )
    out = _pallas_call_32(
        kernel,
        [
            alive0,
            creation0,
            finish0,
            t0[:, None],
            t_exp[:, None],
            t_end[:, None],
            skip[:, None],
            dts,
            warms,
            colds,
        ],
        grid=grid,
        in_specs=[
            state_spec,
            state_spec,
            fin_spec,
            t_spec,
            t_spec,
            t_spec,
            t_spec,
            samp_spec,
            samp_spec,
            samp_spec,
        ],
        out_specs=[state_spec, state_spec, fin_spec, t_spec, acc_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, Mp), jnp.float32),
            jax.ShapeDtypeStruct((R, Mp), jnp.float32),
            jax.ShapeDtypeStruct((R, c * Mp), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, PAR_ACC_COLS), jnp.float32),
        ],
        interpret=interpret,
    )
    return out[4]


# ---------------------------------------------------------------------------
# Fleet kernel: functions as the rows of one replica block, shared cluster
# capacity as a cross-row sum (DESIGN.md §13)
# ---------------------------------------------------------------------------


def _fleet_kernel(
    *refs,
    n_steps: int,
    queue_depth: int,
    prestamped: bool,
    crashes: bool = False,
    cap_steps: int = 0,
):
    """One fleet (cell × replica) = one ``BLOCK_R``-row block: row f is
    function f's ``[M]`` pool (padded functions get ``limit=0``), every
    row carries the SAME merged event stream, and ``fids`` names the
    acting row per event.  The shared capacity is the block-wide
    ``alive.sum()`` — exact in f32 because occupancy counts are small
    integers — gating cold starts against the per-row ``ncl``.  With
    ``queue_depth > 0`` three revisited ``[Rb, Q]`` FIFO blocks (enqueue
    time + the held warm/cold samples) drain ahead of each arrival.
    """
    Q = queue_depth
    if Q and (crashes or cap_steps):
        raise AssertionError("fleet faults are incompatible with queue_depth > 0")
    (
        alive_in,
        creation_in,
        busy_in,
        t0_ref,
        texp_ref,
        lim_ref,
        ncl_ref,
        tend_ref,
        skip_ref,
    ) = refs[:9]
    i = 9
    crate_ref = None
    if crashes:
        crate_ref = refs[i]
        i += 1
    cape_ref = capv_ref = None
    if cap_steps:
        cape_ref, capv_ref = refs[i], refs[i + 1]
        i += 2
    dt_ref, fid_ref, warm_ref, cold_ref = refs[i : i + 4]
    i += 4
    crashu_ref = None
    if crashes:
        crashu_ref = refs[i]
        i += 1
    outs = refs[i:]
    doom_out = None
    if crashes:
        *outs, doom_out = outs  # the doom plane rides last
    if Q:
        alive_out, creation_out, busy_out, t_out, acc_out, qt_out, qw_out, qc_out = outs
    else:
        alive_out, creation_out, busy_out, t_out, acc_out = outs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        alive_out[...] = alive_in[...]
        creation_out[...] = creation_in[...]
        busy_out[...] = busy_in[...]
        t_out[...] = t0_ref[...]
        acc_out[...] = jnp.zeros(acc_out.shape, acc_out.dtype)
        if crashes:
            # fresh pools carry no crash clock; cold starts stamp one
            doom_out[...] = jnp.full(doom_out.shape, jnp.inf, doom_out.dtype)
        if Q:
            qt_out[...] = jnp.full(qt_out.shape, NEG, qt_out.dtype)
            qw_out[...] = jnp.full(qw_out.shape, NEG, qw_out.dtype)
            qc_out[...] = jnp.full(qc_out.shape, NEG, qc_out.dtype)

    alive = alive_out[...]
    creation = creation_out[...]
    busy = busy_out[...]
    t = t_out[...][:, 0]
    acc0 = acc_out[...]
    t_exp = texp_ref[...][:, 0]  # [Rb]
    limit = lim_ref[...][:, 0]
    ncl = ncl_ref[...][:, 0]
    t_end = tend_ref[...][:, 0]
    skip = skip_ref[...][:, 0]
    crate = crate_ref[...][:, 0] if crashes else None  # [Rb]
    # cap_e carries a leading 0.0 edge so the segment lookup is a plain
    # count (launcher prepends it); cap_v is the per-segment ceiling
    cap_e = cape_ref[...] if cap_steps else None  # [Rb, cap_steps]
    cap_v = capv_ref[...] if cap_steps else None  # [Rb, cap_steps]
    doom0 = doom_out[...] if crashes else None
    slot_iota = _iota(alive.shape, 1)
    rid = _iota(alive.shape, 0)[:, 0]  # [Rb]
    # the peak column is a MAX accumulator: seed from the prior chunk
    peak0 = jnp.max(acc0[:, FLEET_ACC_COLS - 1])
    if Q:
        q_iota = _iota((alive.shape[0], Q), 1)
        qt0, qw0, qc0 = qt_out[...], qw_out[...], qc_out[...]

    def routing(alive, creation, busy, t_new):
        idle = (alive > 0) & (busy <= t_new[:, None])
        best = jnp.max(jnp.where(idle, creation, NEG), axis=1)
        any_idle = best > NEG * 0.5
        is_best = idle & (creation >= best[:, None]) & any_idle[:, None]
        first_best = jnp.min(jnp.where(is_best, slot_iota, 1e9), axis=1)
        free = alive <= 0
        first_free = jnp.min(jnp.where(free, slot_iota, 1e9), axis=1)
        any_free = first_free < 1e9
        n_alive = alive.sum(axis=1)
        return any_idle, first_best, any_free, first_free, n_alive

    def step(i, carry):
        if Q:
            alive, creation, busy, t, acc, peak, qt, qw, qc = carry
        elif crashes:
            alive, creation, busy, t, acc, peak, doom = carry
        else:
            alive, creation, busy, t, acc, peak = carry
            doom = None
        dt = _event_col(dt_ref, i)
        fid = _event_col(fid_ref, i)
        warm_s = _event_col(warm_ref, i)
        cold_s = _event_col(cold_ref, i)
        act = fid == rid
        t_new = dt if prestamped else t + dt

        lo = jnp.clip(t, skip, t_end)
        hi = jnp.clip(t_new, skip, t_end)
        expire = busy + t_exp[:, None]
        if crashes:
            # a crashed instance stops accruing run/idle time at its doom
            stop = jnp.minimum(hi[:, None], doom)
            run_t = jnp.clip(jnp.minimum(busy, stop) - lo[:, None], 0.0, None)
            idle_t = jnp.clip(
                jnp.minimum(expire, stop) - jnp.maximum(busy, lo[:, None]),
                0.0,
                None,
            )
        else:
            run_t = jnp.clip(
                jnp.minimum(busy, hi[:, None]) - lo[:, None], 0.0, None
            )
            idle_t = jnp.clip(
                jnp.minimum(expire, hi[:, None]) - jnp.maximum(busy, lo[:, None]),
                0.0,
                None,
            )
        run_sum = (run_t * alive).sum(axis=1)
        idle_sum = (idle_t * alive).sum(axis=1)

        # expirations (and crash exits: whichever clock fires first)
        exit_time = jnp.minimum(expire, doom) if crashes else expire
        expired = (alive > 0) & (exit_time <= t_new[:, None])
        if crashes:
            crash_ok = (
                expired
                & (doom < expire)
                & (doom > skip[:, None])
                & (doom <= t_end[:, None])
            )
            n_crash = crash_ok.astype(jnp.float32).sum(axis=1)
        alive = jnp.where(expired, 0.0, alive)
        cc = t_new > skip

        if cap_steps:
            # cluster capacity churn: the ceiling applies to the whole
            # block (one fleet), so idle instances are ranked fleet-wide —
            # the flat id row*M + slot breaks creation ties exactly like
            # the f64 scan's flattened [F*M] pool (DESIGN.md §15).  The
            # static loop over block rows keeps every tensor rank <= 3.
            seg = (cap_e <= t_new[:, None]).astype(jnp.float32).sum(axis=1) - 1.0
            cap_col = _iota(cap_v.shape, 1)
            cap_now = (cap_v * (cap_col == seg[:, None])).sum(axis=1)  # [Rb]
            idle_now = (alive > 0) & (busy <= t_new[:, None])
            over = alive.sum() - cap_now  # [Rb] (all rows agree)
            row2 = _iota(alive.shape, 0)
            flat = row2 * float(alive.shape[1]) + slot_iota  # [Rb, M]
            rank = jnp.zeros(alive.shape, jnp.float32)
            for br in range(alive.shape[0]):
                cre_b = creation[br][None, None, :]  # row br's slots
                flat_b = flat[br][None, None, :]
                idle_b = idle_now[br][None, None, :]
                newer = (cre_b > creation[:, :, None]) | (
                    (cre_b == creation[:, :, None])
                    & (flat_b < flat[:, :, None])
                )
                rank = rank + (idle_b & newer).astype(jnp.float32).sum(axis=2)
            evict = idle_now & (rank < over[:, None]) & (t_new <= t_end)[:, None]
            n_evict = (
                (evict & (t_new > skip)[:, None]).astype(jnp.float32).sum(axis=1)
            )
            alive = jnp.where(evict, 0.0, alive)

        if Q:
            # FIFO drain ahead of the arrival: at most one row acts per
            # event, and freed capacity can only serve the head, so Q
            # in-order passes are exact (later passes no-op when stuck)
            def drain(_, dcarry):
                alive, creation, busy, acc, qt, qw, qc = dcarry
                any_idle, first_best, any_free, first_free, n_alive = routing(
                    alive, creation, busy, t_new
                )
                cluster = alive.sum()
                ht, hw, hc = qt[:, 0], qw[:, 0], qc[:, 0]
                has = (ht > NEG * 0.5) & act & (t_new <= t_end)
                can_warm = has & any_idle
                can_cold = (
                    has
                    & (~any_idle)
                    & (n_alive < limit)
                    & any_free
                    & (cluster < ncl)
                )
                serve = can_warm | can_cold
                chosen = jnp.where(can_warm, first_best, first_free)
                service = jnp.where(can_warm, hw, hc)
                sel = (slot_iota == chosen[:, None]) & serve[:, None]
                busy = jnp.where(sel, (t_new + service)[:, None], busy)
                creation = jnp.where(
                    sel & can_cold[:, None], t_new[:, None], creation
                )
                alive = jnp.where(sel & can_cold[:, None], 1.0, alive)
                zero = jnp.zeros_like(run_sum)
                delta = jnp.stack(
                    [
                        (can_cold & cc).astype(jnp.float32),
                        (can_warm & cc).astype(jnp.float32),
                        zero,
                        zero,
                        zero,
                        jnp.where(can_cold & cc, hc, 0.0),
                        jnp.where(can_warm & cc, hw, 0.0),
                        zero,
                        zero,
                        zero,
                        (serve & cc).astype(jnp.float32),
                        jnp.where(serve & cc, t_new - ht, 0.0),
                        zero,
                    ],
                    axis=1,
                )
                neg_col = jnp.full((alive.shape[0], 1), NEG, qt.dtype)
                shift = lambda qx: jnp.where(
                    serve[:, None],
                    jnp.concatenate([qx[:, 1:], neg_col], axis=1),
                    qx,
                )
                return alive, creation, busy, acc + delta, shift(qt), shift(qw), shift(qc)

            alive, creation, busy, acc, qt, qw, qc = jax.lax.fori_loop(
                0, Q, drain, (alive, creation, busy, acc, qt, qw, qc)
            )

        any_idle, first_best, any_free, first_free, n_alive = routing(
            alive, creation, busy, t_new
        )
        cluster = alive.sum()
        active = (t_new <= t_end) & act
        can_cold = (~any_idle) & (n_alive < limit) & any_free & (cluster < ncl)
        if cap_steps:
            # admission gate while degraded: no cold start over the ceiling
            can_cold = can_cold & (cluster < cap_now)
        overflow = (~any_idle) & (n_alive < limit) & (~any_free) & active
        is_warm = any_idle & active
        is_cold = can_cold & active
        if Q:
            qlen = (qt > NEG * 0.5).sum(axis=1)
            can_enq = (~any_idle) & (~can_cold) & (qlen < Q)
            is_enq = can_enq & active
            is_reject = (~any_idle) & (~can_cold) & (~can_enq) & active
        else:
            is_enq = jnp.zeros_like(active)
            is_reject = (~any_idle) & (~can_cold) & active

        chosen = jnp.where(is_warm, first_best, first_free)
        service = jnp.where(is_warm, warm_s, cold_s)
        assign = is_warm | is_cold
        sel = (slot_iota == chosen[:, None]) & assign[:, None]
        busy = jnp.where(sel, (t_new + service)[:, None], busy)
        creation = jnp.where(sel & is_cold[:, None], t_new[:, None], creation)
        alive = jnp.where(sel & is_cold[:, None], 1.0, alive)
        if crashes:
            # Exp(crash_rate) lifetime stamped at cold start (memoryless ⇒
            # hazard-equivalent); warm hits keep the instance's old doom.
            # No reliability layer in the fleet: an interrupted attempt is
            # one whose instance dies before the service completes.
            crash_i = _event_col(crashu_ref, i)
            life = -jnp.log(1.0 - crash_i) / crate
            doom = jnp.where(
                sel & is_cold[:, None], (t_new + life)[:, None], doom
            )
            doom_chosen = jnp.min(jnp.where(sel, doom, jnp.inf), axis=1)
            interrupted = assign & (doom_chosen < t_new + service)
        if Q:
            qsel = (q_iota == qlen[:, None]) & is_enq[:, None]
            qt = jnp.where(qsel, t_new[:, None], qt)
            qw = jnp.where(qsel, warm_s[:, None], qw)
            qc = jnp.where(qsel, cold_s[:, None], qc)
        peak = jnp.maximum(peak, alive.sum())

        zero = jnp.zeros_like(run_sum)
        delta = jnp.stack(
            [
                (is_cold & cc).astype(jnp.float32),
                (is_warm & cc).astype(jnp.float32),
                (is_reject & cc).astype(jnp.float32),
                run_sum,
                idle_sum,
                jnp.where(is_cold & cc, cold_s, 0.0),
                jnp.where(is_warm & cc, warm_s, 0.0),
                overflow.astype(jnp.float32),
                (active & cc).astype(jnp.float32),
                (is_enq & cc).astype(jnp.float32),
                zero,
                zero,
                zero,
            ],
            axis=1,
        )
        if crashes or cap_steps:
            # fault columns ride after the fleet layout (DESIGN.md §15)
            f_crash = n_crash if crashes else zero
            f_evict = n_evict if cap_steps else zero
            f_int = (interrupted & cc).astype(jnp.float32) if crashes else zero
            delta = jnp.concatenate(
                [delta, jnp.stack([f_crash, f_evict, f_int], axis=1)], axis=1
            )
        acc = acc + delta
        if Q:
            return alive, creation, busy, t_new, acc, peak, qt, qw, qc
        if crashes:
            return alive, creation, busy, t_new, acc, peak, doom
        return alive, creation, busy, t_new, acc, peak

    if Q:
        carry = (alive, creation, busy, t, acc0, peak0, qt0, qw0, qc0)
        alive, creation, busy, t, acc, peak, qt, qw, qc = jax.lax.fori_loop(
            0, n_steps, step, carry
        )
        qt_out[...] = qt
        qw_out[...] = qw
        qc_out[...] = qc
    elif crashes:
        alive, creation, busy, t, acc, peak, doom = jax.lax.fori_loop(
            0, n_steps, step, (alive, creation, busy, t, acc0, peak0, doom0)
        )
        doom_out[...] = doom
    else:
        alive, creation, busy, t, acc, peak = jax.lax.fori_loop(
            0, n_steps, step, (alive, creation, busy, t, acc0, peak0)
        )
    col_iota = _iota(acc.shape, 1)
    acc = jnp.where(col_iota == float(FLEET_ACC_COLS - 1), peak, acc)
    alive_out[...] = alive
    creation_out[...] = creation
    busy_out[...] = busy
    t_out[...] = t[:, None]
    acc_out[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=(
        "slots",
        "queue_depth",
        "block_r",
        "block_k",
        "interpret",
        "prestamped",
    ),
)
def fleet_sweep_pallas(
    t_exp,  # f32 [R] per-row (function) expiration threshold
    limit,  # f32 [R] per-row function concurrency limit (0 = padded row)
    ncl,  # f32 [R] shared cluster capacity (same across a block; 1e30 = inf)
    t_end,  # f32 [R]
    skip,  # f32 [R]
    dts,  # f32 [R, K] merged stream: gaps, or absolute times if prestamped
    fids,  # f32 [R, K] acting-row id per event (same stream across a block)
    warms,  # f32 [R, K]
    colds,  # f32 [R, K]
    crash_rate=None,  # f32 [R] per-row crash hazard (faults, DESIGN.md §15)
    crash_u=None,  # f32 [R, K] per-event crash-lifetime uniforms (faults)
    cap_edges=None,  # f32 [R, E] capacity-profile step times (faults)
    cap_values=None,  # f32 [R, E+1] per-segment capacity ceilings (faults)
    *,
    slots: int,
    queue_depth: int = 0,
    block_r: int = 8,
    block_k: int = 512,
    interpret: bool = False,
    prestamped: bool = False,
):
    """Fleet block launch: ``R = fleets × block_r`` rows, one fleet per
    block.  Returns ``(acc[R, cols], qt_final[R, Q] | None)`` where
    ``cols = FLEET_ACC_COLS`` plus ``FAULT_COLS`` when faults are on.
    Every fleet axis value (thresholds, limits, capacity, horizon) is a
    traced per-row input, so a fleet × threshold grid is ONE trace.
    """
    TRACE_COUNTS["fleet_sweep_pallas"] += 1
    R, K = dts.shape
    M = slots
    Q = queue_depth
    crashes = crash_u is not None
    cap_steps = 0 if cap_values is None else cap_values.shape[1]
    assert not (Q and (crashes or cap_steps)), (
        "fleet faults are incompatible with queue_depth > 0"
    )
    assert R % block_r == 0, (R, block_r)
    assert K % block_k == 0, (K, block_k)
    assert block_k <= LANE or block_k % LANE == 0, block_k  # _event_col tiles
    grid = (R // block_r, K // block_k)
    acc_cols = FLEET_ACC_COLS + (FAULT_COLS if crashes or cap_steps else 0)

    state_spec = pl.BlockSpec((block_r, M), lambda r, k: (r, 0))
    samp_spec = pl.BlockSpec((block_r, block_k), lambda r, k: (r, k))
    t_spec = pl.BlockSpec((block_r, 1), lambda r, k: (r, 0))
    acc_spec = pl.BlockSpec((block_r, acc_cols), lambda r, k: (r, 0))

    kernel = functools.partial(
        _fleet_kernel,
        n_steps=block_k,
        queue_depth=Q,
        prestamped=prestamped,
        crashes=crashes,
        cap_steps=cap_steps,
    )
    frozen = jnp.full((R, M), NEG, jnp.float32)
    inputs = [
        jnp.zeros((R, M), jnp.float32),
        frozen,
        frozen,
        jnp.zeros((R, 1), jnp.float32),
        t_exp[:, None],
        limit[:, None],
        ncl[:, None],
        t_end[:, None],
        skip[:, None],
    ]
    in_specs = [state_spec, state_spec, state_spec] + [t_spec] * 6
    if crashes:
        inputs.append(
            jnp.broadcast_to(jnp.asarray(crash_rate, jnp.float32), (R,))[:, None]
        )
        in_specs.append(t_spec)
    if cap_steps:
        cap_spec = pl.BlockSpec((block_r, cap_steps), lambda r, k: (r, 0))
        # prepend the 0.0 edge so the kernel's segment lookup is a count
        inputs.append(
            jnp.concatenate(
                [
                    jnp.zeros((R, 1), jnp.float32),
                    jnp.asarray(cap_edges, jnp.float32),
                ],
                axis=1,
            )
        )
        inputs.append(jnp.asarray(cap_values, jnp.float32))
        in_specs += [cap_spec, cap_spec]
    inputs += [dts, fids, warms, colds]
    in_specs += [samp_spec] * 4
    if crashes:
        inputs.append(jnp.asarray(crash_u, jnp.float32))
        in_specs.append(samp_spec)
    out_specs = [state_spec, state_spec, state_spec, t_spec, acc_spec]
    out_shape = [
        jax.ShapeDtypeStruct((R, M), jnp.float32),
        jax.ShapeDtypeStruct((R, M), jnp.float32),
        jax.ShapeDtypeStruct((R, M), jnp.float32),
        jax.ShapeDtypeStruct((R, 1), jnp.float32),
        jax.ShapeDtypeStruct((R, acc_cols), jnp.float32),
    ]
    if Q:
        q_spec = pl.BlockSpec((block_r, Q), lambda r, k: (r, 0))
        out_specs += [q_spec] * 3
        out_shape += [jax.ShapeDtypeStruct((R, Q), jnp.float32)] * 3
    if crashes:
        out_specs.append(state_spec)
        out_shape.append(jax.ShapeDtypeStruct((R, M), jnp.float32))
    out = _pallas_call_32(
        kernel,
        inputs,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )
    return out[4], (out[5] if Q else None)


@register_backend("pallas", engines=("fleet",))
def _pallas_fleet_rows(
    t_exp, limit, ncl, t_end, skip, dts, fids, warms, colds,
    *, slots, queue_depth, prestamped, block_k,
    crash_rate=None, crash_u=None, cap_edges=None, cap_values=None,
):
    """The fleet launcher (``BackendSpec.launch_for("fleet")``): chunk-pad
    the merged stream and run :func:`fleet_sweep_pallas`.  Rows arrive
    pre-blocked (``C = cells × replicas × BLOCK_R``, padded functions
    inert via ``limit=0``), so only the arrival axis needs padding — the
    1e30 time fill is inert as gap and timestamp alike, and padded fids
    hit row 0 past its horizon (no-ops).  Returns
    ``(acc[C, FLEET_ACC_COLS], qleft[C])``.
    """
    C, n = dts.shape
    assert C % BLOCK_R == 0, (C, BLOCK_R)
    block_k = lane_chunk(block_k, n)
    pad_k = (-n) % block_k

    def pad(x, col_fill):
        if pad_k:
            x = jnp.concatenate(
                [x, jnp.full((x.shape[0], pad_k), col_fill, x.dtype)], axis=1
            )
        return x

    fault_kw = {}
    if crash_u is not None:
        # padded events sit past t_end (1e30 time fill), so any doom they
        # stamp is > t_end and never counted; 0.0 keeps the log finite
        fault_kw["crash_rate"] = jnp.asarray(crash_rate, jnp.float32)
        fault_kw["crash_u"] = pad(jnp.asarray(crash_u, jnp.float32), 0.0)
    if cap_values is not None:
        fault_kw["cap_edges"] = jnp.asarray(cap_edges, jnp.float32)
        fault_kw["cap_values"] = jnp.asarray(cap_values, jnp.float32)
    acc, qt = fleet_sweep_pallas(
        jnp.asarray(t_exp, jnp.float32),
        jnp.asarray(limit, jnp.float32),
        jnp.asarray(ncl, jnp.float32),
        jnp.asarray(t_end, jnp.float32),
        jnp.asarray(skip, jnp.float32),
        pad(jnp.asarray(dts, jnp.float32), 1e30),
        pad(jnp.asarray(fids, jnp.float32), 0.0),
        pad(jnp.asarray(warms, jnp.float32), 1.0),
        pad(jnp.asarray(colds, jnp.float32), 1.0),
        slots=slots,
        queue_depth=queue_depth,
        block_r=BLOCK_R,
        block_k=block_k,
        interpret=_interpret(),
        prestamped=prestamped,
        **fault_kw,
    )
    if qt is None:
        qleft = jnp.zeros((C,), jnp.float32)
    else:
        qleft = (qt > NEG * 0.5).sum(axis=1).astype(jnp.float32)
    return acc, qleft


@register_backend("pallas", engines=("par",))
def _pallas_par_rows(t_exp, t_end, skip, dts, warms, colds, *, block_k, **kw):
    """The par engine's ``pallas`` row launcher: replica-block row padding
    + arrival-chunk padding around :func:`par_sweep_pallas`."""
    C, n = dts.shape
    block_k = lane_chunk(block_k, n)
    pad_c = (-C) % BLOCK_R
    pad_k = (-n) % block_k

    def pad(x, col_fill):
        if pad_k:
            x = jnp.concatenate(
                [x, jnp.full((x.shape[0], pad_k), col_fill, x.dtype)], axis=1
            )
        return _pad_rows(x, pad_c)

    acc = par_sweep_pallas(
        _pad_rows(t_exp, pad_c, fill=1.0),
        pad(dts, 1e30),
        pad(warms, 1.0),
        pad(colds, 1.0),
        t_end=_pad_rows(t_end, pad_c, fill=1.0),
        skip=_pad_rows(skip, pad_c, fill=1.0),
        block_r=BLOCK_R,
        block_k=block_k,
        interpret=_interpret(),
        **kw,
    )
    return acc[:C]
