"""Pure-jnp oracles for every Pallas kernel (single import point for the
kernel test-suite).  The model-level references live next to their blocks;
this module re-exports them plus the FaaS-kernel reference, so each kernel
has a ``kernels.ref`` counterpart as required by the repo convention."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.execution import SUBLANE, register_backend
from repro.core import drawplan as dp
from repro.models.attention import (  # noqa: F401
    decode_attention_ref,
    flash_attention_ref,
    naive_attention,
)
from repro.models.rglru import rglru_scan_ref  # noqa: F401
from repro.models.ssm import ssd_chunked_ref  # noqa: F401

NEG = -1e30


def ssd_scan_ref(xd, dA, Bh, Ch, chunk: int = 128):
    """Same pre-folded interface as ``ssd_scan_pallas`` (B/C broadcast to
    heads, xd = x·dt, dA = dt·A) → delegates to the chunked reference."""
    dt_ones = jnp.ones(dA.shape, dA.dtype)
    # reconstruct the (x, dt, A)-style call: ssd_chunked_ref folds dt into
    # x and A internally, so pass xd as x with dt=1 and dA via A-per-step.
    # Easiest exact route: inline the recurrence directly.
    B, L, H, P = xd.shape
    N = Bh.shape[-1]
    state = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(L):  # small-shape oracle only (tests)
        a = jnp.exp(dA[:, t])  # [B,H]
        state = state * a[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xd[:, t], Bh[:, t]
        )
        ys.append(jnp.einsum("bhpn,bhn->bhp", state, Ch[:, t]))
    del dt_ones
    return jnp.stack(ys, axis=1), state


def faas_sweep_ref(
    alive,
    creation,
    busy,
    t0,
    t_exp,  # f32 [R] per-row expiration threshold
    dts,
    warms,
    colds,
    *,
    t_end=float("inf"),  # f32 [R] or scalar — per-row horizon
    skip=0.0,  # f32 [R] or scalar — per-row warm-up exclusion
    window_bounds=None,  # f32 [R, W+1] traced boundaries (irregular OK)
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
    max_concurrency,
    prestamped: bool = False,
    n_windows: int = 0,
    n_grid: int = 0,
    fused_dists=None,  # static ("exp", ...) ×3 → inline draw generation
    fused_k: int = 0,  # static event count when fused (no dts to size from)
):
    """f32 jnp mirror of ``faas_sweep_pallas`` (same arithmetic order, same
    tie-breaks) — bit-comparable on CPU, and the reference the what-if
    sweep's throughput backend is checked against.  ``prestamped`` /
    ``n_windows`` / ``n_grid`` mirror the kernel's absolute-timestamp,
    traced-window-bounds (acc gains ``5*n_windows`` columns: counts plus
    ∫running/∫idle) and transient-curve (``3*n_grid`` columns) extensions;
    ``t_end``/``skip``/the boundary rows are per-row traced values like
    ``t_exp``, so horizon and window-grid sweeps share one compile."""
    from repro.kernels.faas_event_step import FAULT_COLS, NO_CHILD_F, RELY_COLS

    fused = fused_dists is not None
    R, M = alive.shape
    K = fused_k if fused else dts.shape[1]
    reliability = t_timeout is not None
    retries = is_first is not None
    crashes = crash_u is not None
    cap_steps = 0 if cap_values is None else cap_values.shape[1]
    assert not (fused and retries), "fused draws do not serve retry streams"
    assert not (fused and (crashes or cap_steps)), (
        "fused draws do not serve platform faults"
    )
    if fused:
        a_keys, w_keys, c_keys = (
            jnp.asarray(k, jnp.uint32) for k in fused_keys
        )
        a_par, w_par, c_par = (
            jnp.asarray(p, jnp.float32) for p in fused_params
        )
        if reliability:
            f_keys = jnp.asarray(fused_fail_keys, jnp.uint32)
    t_exp = jnp.broadcast_to(jnp.asarray(t_exp, jnp.float32), (R,))
    t_end = jnp.broadcast_to(jnp.asarray(t_end, jnp.float32), (R,))
    skip = jnp.broadcast_to(jnp.asarray(skip, jnp.float32), (R,))
    slot_iota = jnp.broadcast_to(
        jnp.arange(M, dtype=jnp.float32)[None, :], (R, M)
    )
    if reliability:
        t_to = jnp.broadcast_to(jnp.asarray(t_timeout, jnp.float32), (R,))
        p_f = jnp.broadcast_to(jnp.asarray(p_fail, jnp.float32), (R,))
        if not fused:
            fail_u = jnp.asarray(fail_u, jnp.float32)
    if retries:
        is_first = jnp.asarray(is_first, jnp.float32)
        child_pos = jnp.asarray(child_pos, jnp.float32)
        k_iota = jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.float32)[None, :], (R, K)
        )
    if n_windows:
        wb = jnp.asarray(window_bounds, jnp.float32)
        w_lo, w_hi = wb[:, :-1], wb[:, 1:]
    if n_grid:
        g_times = jnp.asarray(grid_times, jnp.float32)
    if crashes:
        crate = jnp.broadcast_to(jnp.asarray(crash_rate, jnp.float32), (R,))
        crash_u = jnp.asarray(crash_u, jnp.float32)
    if cap_steps:
        # same leading 0.0 edge the Pallas launcher prepends, so the
        # segment lookup is the identical plain count
        cap_e = jnp.concatenate(
            [jnp.zeros((R, 1), jnp.float32), jnp.asarray(cap_edges, jnp.float32)],
            axis=1,
        )
        cap_v = jnp.asarray(cap_values, jnp.float32)

    def step(i, carry):
        alive, creation, busy, t, acc = carry[:5]
        rest = list(carry[5:])
        act = rest.pop(0) if retries else None
        doom = rest.pop(0) if crashes else None
        if fused:
            # same counter scheme as the Pallas kernel: global event index
            # (chunk base 0 here — the ref is unchunked), bitwise-equal
            gk = i.astype(jnp.uint32)
            a_u0, a_u1 = dp.event_uniforms(a_keys[:, 0], a_keys[:, 1], gk)
            w_u0, w_u1 = dp.event_uniforms(w_keys[:, 0], w_keys[:, 1], gk)
            c_u0, c_u1 = dp.event_uniforms(c_keys[:, 0], c_keys[:, 1], gk)
            dt_i = dp.sample_dist(
                fused_dists[0], a_u0, a_u1, a_par[:, 0], a_par[:, 1]
            )
            warm_i = dp.sample_dist(
                fused_dists[1], w_u0, w_u1, w_par[:, 0], w_par[:, 1]
            )
            cold_i = dp.sample_dist(
                fused_dists[2], c_u0, c_u1, c_par[:, 0], c_par[:, 1]
            )
            if reliability:
                fail_i, _ = dp.event_uniforms(f_keys[:, 0], f_keys[:, 1], gk)
        else:
            dt_i, warm_i, cold_i = dts[:, i], warms[:, i], colds[:, i]
            if reliability:
                fail_i = fail_u[:, i]
        t_new = dt_i if prestamped else t + dt_i
        lo = jnp.clip(t, skip, t_end)
        hi = jnp.clip(t_new, skip, t_end)
        expire = busy + t_exp[:, None]
        if crashes:
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
        if n_windows:
            lo_e = jnp.minimum(t, t_end)
            hi_e = jnp.minimum(t_new, t_end)
            wlo = jnp.maximum(w_lo, lo_e[:, None])
            whi = jnp.minimum(w_hi, hi_e[:, None])
            run_w = jnp.clip(
                jnp.minimum(busy[:, None, :], whi[:, :, None]) - wlo[:, :, None],
                0.0,
                None,
            )
            idle_w = jnp.clip(
                jnp.minimum(expire[:, None, :], whi[:, :, None])
                - jnp.maximum(busy[:, None, :], wlo[:, :, None]),
                0.0,
                None,
            )
            w_run = (run_w * alive[:, None, :]).sum(axis=2)
            w_idle = (idle_w * alive[:, None, :]).sum(axis=2)
        if n_grid:
            in_win = (g_times > t[:, None]) & (
                g_times <= jnp.minimum(t_new, t_end)[:, None]
            )
            live_g = (alive[:, None, :] > 0) & (
                expire[:, None, :] > g_times[:, :, None]
            )
            running_g = (live_g & (busy[:, None, :] > g_times[:, :, None])).sum(
                axis=2
            )
            idle_g = (live_g & (busy[:, None, :] <= g_times[:, :, None])).sum(
                axis=2
            )
            g_run = jnp.where(in_win, running_g.astype(jnp.float32), 0.0)
            g_idle = jnp.where(in_win, idle_g.astype(jnp.float32), 0.0)
            g_cold = (in_win & (idle_g == 0)).astype(jnp.float32)
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
        if cap_steps:
            seg = (cap_e <= t_new[:, None]).astype(jnp.float32).sum(axis=1) - 1.0
            cap_col = jax.lax.broadcasted_iota(jnp.float32, cap_v.shape, 1)
            cap_now = (cap_v * (cap_col == seg[:, None])).sum(axis=1)
            idle_now = (alive > 0) & (busy <= t_new[:, None])
            over = alive.sum(axis=1) - cap_now
            cre_a = creation[:, :, None]
            cre_b = creation[:, None, :]
            shape3 = (creation.shape[0], creation.shape[1], creation.shape[1])
            ia = jax.lax.broadcasted_iota(jnp.float32, shape3, 1)
            ib = jax.lax.broadcasted_iota(jnp.float32, shape3, 2)
            newer = (cre_b > cre_a) | ((cre_b == cre_a) & (ib < ia))
            rank = (
                (idle_now[:, None, :] & newer).astype(jnp.float32).sum(axis=2)
            )
            evict = (
                idle_now
                & (rank < over[:, None])
                & (t_new <= t_end)[:, None]
            )
            n_evict = (
                (evict & (t_new > skip)[:, None]).astype(jnp.float32).sum(axis=1)
            )
            alive = jnp.where(evict, 0.0, alive)
        idle = (alive > 0) & (busy <= t_new[:, None])
        best = jnp.max(jnp.where(idle, creation, NEG), axis=1)
        any_idle = best > NEG * 0.5
        is_best = idle & (creation >= best[:, None]) & any_idle[:, None]
        first_best = jnp.min(jnp.where(is_best, slot_iota, 1e9), axis=1)
        free = alive <= 0
        any_free = free.any(axis=1)
        first_free = jnp.min(jnp.where(free, slot_iota, 1e9), axis=1)
        n_alive = alive.sum(axis=1)
        active = t_new <= t_end
        if retries:
            first_i = is_first[:, i]
            child = child_pos[:, i]
            gf = i.astype(jnp.float32)
            act_i = jnp.where(k_iota == gf, act, 0.0).sum(axis=1)
            active = active & ((first_i > 0) | (act_i > 0))
        counted = t_new > skip
        can_cold = (~any_idle) & (n_alive < max_concurrency) & any_free
        if cap_steps:
            can_cold = can_cold & (n_alive < cap_now)
        overflow = (~any_idle) & (n_alive < max_concurrency) & (~any_free) & active
        is_warm = any_idle & active
        is_cold = can_cold & active
        is_reject = (~any_idle) & (~can_cold) & active
        chosen = jnp.where(is_warm, first_best, first_free)
        service = jnp.where(is_warm, warm_i, cold_i)
        if reliability:
            occupancy = jnp.minimum(service, t_to)
        else:
            occupancy = service
        assign = is_warm | is_cold
        sel = (slot_iota == chosen[:, None]) & assign[:, None]
        busy = jnp.where(sel, (t_new + occupancy)[:, None], busy)
        creation = jnp.where(sel & is_cold[:, None], t_new[:, None], creation)
        alive = jnp.where(sel & is_cold[:, None], 1.0, alive)
        if crashes:
            crash_i = crash_u[:, i]
            life = -jnp.log(1.0 - crash_i) / crate
            doom = jnp.where(
                sel & is_cold[:, None], (t_new + life)[:, None], doom
            )
            doom_chosen = jnp.min(jnp.where(sel, doom, jnp.inf), axis=1)
        cc = counted
        if reliability:
            timed_out = assign & (service > t_to)
            failed = assign & ~timed_out & (fail_i < p_f)
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
            cold_resp = jnp.minimum(cold_i, t_to)
            warm_resp = jnp.minimum(warm_i, t_to)
        else:
            if crashes:
                interrupted = assign & (doom_chosen < t_new + occupancy)
            cold_resp, warm_resp = cold_i, warm_i
        delta = jnp.stack(
            [
                (is_cold & cc).astype(jnp.float32),
                (is_warm & cc).astype(jnp.float32),
                (is_reject & cc).astype(jnp.float32),
                run_sum,
                idle_sum,
                jnp.where(is_cold & cc, cold_resp, 0.0),
                jnp.where(is_warm & cc, warm_resp, 0.0),
                overflow.astype(jnp.float32),
            ],
            axis=1,
        )
        if n_windows:
            onehot = (
                (t_new[:, None] >= w_lo) & (t_new[:, None] < w_hi)
            ) & active[:, None]
            w_cold = (onehot & is_cold[:, None]).astype(jnp.float32)
            w_served = (onehot & (is_cold | is_warm)[:, None]).astype(
                jnp.float32
            )
            w_arr = onehot.astype(jnp.float32)  # includes rejects
            delta = jnp.concatenate(
                [delta, w_cold, w_served, w_arr, w_run, w_idle], axis=1
            )
        if n_grid:
            delta = jnp.concatenate([delta, g_run, g_idle, g_cold], axis=1)
        if reliability:
            if retries:
                has_child = child < NO_CHILD_F
                r_retry = (first_i <= 0) & active & cc
                r_abandon = trigger & ~has_child & cc
                hit = (k_iota == child[:, None]) & trigger[:, None]
                act = jnp.where(hit, 1.0, act)
            else:
                r_retry = jnp.zeros_like(trigger)
                r_abandon = trigger & cc
            delta = jnp.concatenate(
                [
                    delta,
                    jnp.stack(
                        [
                            (timed_out & cc).astype(jnp.float32),
                            (failed & cc).astype(jnp.float32),
                            r_retry.astype(jnp.float32),
                            r_abandon.astype(jnp.float32),
                        ],
                        axis=1,
                    ),
                ],
                axis=1,
            )
        if crashes or cap_steps:
            zero = jnp.zeros_like(run_sum)
            f_crash = n_crash if crashes else zero
            f_evict = n_evict if cap_steps else zero
            f_int = (
                (interrupted & cc).astype(jnp.float32) if crashes else zero
            )
            delta = jnp.concatenate(
                [delta, jnp.stack([f_crash, f_evict, f_int], axis=1)], axis=1
            )
        acc = acc + delta
        out = (alive, creation, busy, t_new, acc)
        if retries:
            out = out + (act,)
        if crashes:
            out = out + (doom,)
        return out

    acc0 = jnp.zeros(
        (
            R,
            8
            + 5 * n_windows
            + 3 * n_grid
            + (RELY_COLS if reliability else 0)
            + (FAULT_COLS if crashes or cap_steps else 0),
        ),
        jnp.float32,
    )
    carry0 = (alive, creation, busy, t0, acc0)
    if retries:
        carry0 = carry0 + (jnp.zeros((R, K), jnp.float32),)
    if crashes:
        carry0 = carry0 + (jnp.full((R, M), jnp.inf, jnp.float32),)
    out = jax.lax.fori_loop(0, K, step, carry0)
    return out[:5]


@functools.lru_cache(maxsize=1)
def _sweep_ref_jit():
    def counted(*args, **kw):
        # the counter lives on the scenario-level Counter so the test
        # suite pins block-backend re-traces in one place
        from repro.core.scenario import TRACE_COUNTS

        TRACE_COUNTS["sweep_block_ref"] += 1
        return faas_sweep_ref(*args, **kw)

    return jax.jit(
        counted,
        static_argnames=(
            "max_concurrency",
            "prestamped",
            "n_windows",
            "n_grid",
            "fused_dists",
            "fused_k",
        ),
    )


@register_backend(
    "ref",
    precision="f32",
    kind="block",
    shardable=True,
    description="jnp mirror of the Pallas block kernel (bit-comparable)",
    engines=("scan", "temporal"),
)
def _ref_sweep_rows(
    alive0, creation0, busy0, t0, t_exp, t_end, skip, dts, warms, colds,
    *, block_k, window_bounds=None, grid_times=None, fused=None,
    t_timeout=None, p_fail=None, fail_u=None, is_first=None, child_pos=None,
    crash_rate=None, crash_u=None, cap_edges=None, cap_values=None,
    **kw,
):
    """The sweep engine's ``ref`` row launcher (``BackendSpec.launch``):
    steps as many events as the Pallas kernel does (its
    :func:`~repro.kernels.faas_event_step.sweep_layout`), on rows padded
    to whole sublane tiles.  Serves both the steady-state (scan) and
    transient (temporal, via ``grid_times``) engines.  With ``fused``
    (DrawPlan lowering dict, DESIGN.md §12) draws are regenerated inline
    from the counter scheme and the return value is ``(acc, t_final)``
    for the coverage guard."""
    from repro.kernels.faas_event_step import NO_CHILD_F, _pad_rows, sweep_layout

    C = alive0.shape[0]
    pad_c = (-C) % SUBLANE
    if fused is not None:
        n = int(fused["n_steps"])
        Kp = sweep_layout(C, n, block_k)["stepped"]
        row_pad = lambda x: _pad_rows(x, pad_c, fill=1.0)
        rely_kw = {}
        if t_timeout is not None:
            rely_kw = dict(
                t_timeout=row_pad(t_timeout),
                p_fail=_pad_rows(p_fail, pad_c, fill=0.0),
            )
        out = _sweep_ref_jit()(
            _pad_rows(alive0, pad_c),
            _pad_rows(creation0, pad_c),
            _pad_rows(busy0, pad_c),
            _pad_rows(t0, pad_c, fill=0.0),
            row_pad(t_exp),
            None,
            None,
            None,
            t_end=row_pad(t_end),
            skip=row_pad(skip),
            window_bounds=(
                None if window_bounds is None else _pad_rows(window_bounds, pad_c)
            ),
            grid_times=(
                None if grid_times is None else _pad_rows(grid_times, pad_c)
            ),
            fused_dists=tuple(fused["dists"]),
            fused_k=Kp,
            fused_keys=tuple(
                _pad_rows(jnp.asarray(k, jnp.uint32), pad_c)
                for k in fused["keys"]
            ),
            fused_params=tuple(
                _pad_rows(jnp.asarray(p, jnp.float32), pad_c)
                for p in fused["params"]
            ),
            fused_fail_keys=(
                None
                if fused.get("fail_keys") is None
                else _pad_rows(jnp.asarray(fused["fail_keys"], jnp.uint32), pad_c)
            ),
            **rely_kw,
            **kw,
        )
        return out[4][:C], out[3][:C]
    n = dts.shape[1]
    pad_k = sweep_layout(C, n, block_k)["stepped"] - n

    def pad(x, col_fill):
        if pad_k:
            x = jnp.concatenate(
                [x, jnp.full((x.shape[0], pad_k), col_fill, x.dtype)], axis=1
            )
        return _pad_rows(x, pad_c)

    row_pad = lambda x: _pad_rows(x, pad_c, fill=1.0)
    rely_kw = {}
    if t_timeout is not None:
        # same inert sample fills as the Pallas launcher: fail_u=1.0 never
        # fails, is_first=0 keeps padded events inactive, NO_CHILD never
        # scatters
        rely_kw = dict(
            t_timeout=row_pad(t_timeout),
            p_fail=_pad_rows(p_fail, pad_c, fill=0.0),
            fail_u=pad(fail_u, 1.0),
        )
        if is_first is not None:
            rely_kw.update(
                is_first=pad(is_first, 0.0),
                child_pos=pad(child_pos, NO_CHILD_F),
            )
    fault_kw = {}
    if crash_u is not None:
        fault_kw.update(
            crash_rate=row_pad(crash_rate), crash_u=pad(crash_u, 0.0)
        )
    if cap_values is not None:
        fault_kw.update(
            cap_edges=_pad_rows(jnp.asarray(cap_edges, jnp.float32), pad_c),
            cap_values=_pad_rows(jnp.asarray(cap_values, jnp.float32), pad_c),
        )
    out = _sweep_ref_jit()(
        _pad_rows(alive0, pad_c),
        _pad_rows(creation0, pad_c),
        _pad_rows(busy0, pad_c),
        _pad_rows(t0, pad_c, fill=0.0),
        row_pad(t_exp),
        pad(dts, 1e30),
        pad(warms, 1.0),
        pad(colds, 1.0),
        t_end=row_pad(t_end),
        skip=row_pad(skip),
        window_bounds=(
            None if window_bounds is None else _pad_rows(window_bounds, pad_c)
        ),
        grid_times=(
            None if grid_times is None else _pad_rows(grid_times, pad_c)
        ),
        **rely_kw,
        **fault_kw,
        **kw,
    )
    return out[4][:C]


def fleet_sweep_ref(
    t_exp,  # f32 [R] per-row (function) expiration threshold
    limit,  # f32 [R] per-row function concurrency limit (0 = padded row)
    ncl,  # f32 [R] shared cluster capacity (same across a group; 1e30 = inf)
    t_end,  # f32 [R]
    skip,  # f32 [R]
    dts,  # f32 [R, K] merged stream: gaps, or absolute times if prestamped
    fids,  # f32 [R, K] acting-row id per event (same stream across a group)
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
    prestamped: bool = False,
):
    """f32 jnp mirror of ``fleet_sweep_pallas`` (DESIGN.md §13): every
    group of ``block_r`` consecutive rows is one fleet (row f = function
    f's pool), the shared capacity is the group-wide occupancy sum —
    bitwise equal to the kernel's block-wide ``alive.sum()`` because
    occupancy counts are small integers in f32 — and the acc layout is
    ``FLEET_ACC_COLS`` (+``FAULT_COLS`` under faults) with the peak
    column as a MAX accumulator."""
    from repro.kernels.faas_event_step import FAULT_COLS, FLEET_ACC_COLS

    R, K = dts.shape
    M = slots
    Q = queue_depth
    crashes = crash_u is not None
    cap_steps = 0 if cap_values is None else cap_values.shape[1]
    assert not (Q and (crashes or cap_steps)), (
        "fleet faults are incompatible with queue_depth > 0"
    )
    assert R % block_r == 0, (R, block_r)
    G = R // block_r
    t_exp = jnp.broadcast_to(jnp.asarray(t_exp, jnp.float32), (R,))
    limit = jnp.broadcast_to(jnp.asarray(limit, jnp.float32), (R,))
    ncl = jnp.broadcast_to(jnp.asarray(ncl, jnp.float32), (R,))
    t_end = jnp.broadcast_to(jnp.asarray(t_end, jnp.float32), (R,))
    skip = jnp.broadcast_to(jnp.asarray(skip, jnp.float32), (R,))
    if crashes:
        crate = jnp.broadcast_to(jnp.asarray(crash_rate, jnp.float32), (R,))
        crash_u = jnp.asarray(crash_u, jnp.float32)
    if cap_steps:
        # leading 0.0 edge keeps the segment lookup a plain count, as the
        # Pallas launcher prepends it
        cap_e = jnp.concatenate(
            [jnp.zeros((R, 1), jnp.float32), jnp.asarray(cap_edges, jnp.float32)],
            axis=1,
        )
        cap_v = jnp.asarray(cap_values, jnp.float32)
    slot_iota = jnp.broadcast_to(
        jnp.arange(M, dtype=jnp.float32)[None, :], (R, M)
    )
    rid = (jnp.arange(R) % block_r).astype(jnp.float32)
    group_sum = lambda x: jnp.repeat(x.reshape(G, block_r).sum(axis=1), block_r)
    # the group's row br, broadcast back over its block_r rows — mirrors
    # the kernel's static ``creation[br]`` row pick inside one block
    sel_grow = lambda x, br: jnp.repeat(
        x.reshape(G, block_r, M)[:, br], block_r, axis=0
    )
    if Q:
        q_iota = jnp.broadcast_to(
            jnp.arange(Q, dtype=jnp.float32)[None, :], (R, Q)
        )

    def routing(alive, creation, busy, t_new):
        idle = (alive > 0) & (busy <= t_new[:, None])
        best = jnp.max(jnp.where(idle, creation, NEG), axis=1)
        any_idle = best > NEG * 0.5
        is_best = idle & (creation >= best[:, None]) & any_idle[:, None]
        first_best = jnp.min(jnp.where(is_best, slot_iota, 1e9), axis=1)
        free = alive <= 0
        any_free = free.any(axis=1)
        first_free = jnp.min(jnp.where(free, slot_iota, 1e9), axis=1)
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
        dt = dts[:, i]
        fid = fids[:, i]
        warm_s = warms[:, i]
        cold_s = colds[:, i]
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
            # cluster capacity churn, ranked fleet-wide (flat id row*M +
            # slot breaks creation ties) — op-for-op with the kernel's
            # static loop over its block rows
            seg = (cap_e <= t_new[:, None]).astype(jnp.float32).sum(axis=1) - 1.0
            cap_col = jnp.broadcast_to(
                jnp.arange(cap_v.shape[1], dtype=jnp.float32)[None, :],
                cap_v.shape,
            )
            cap_now = (cap_v * (cap_col == seg[:, None])).sum(axis=1)
            idle_now = (alive > 0) & (busy <= t_new[:, None])
            over = group_sum(alive.sum(axis=1)) - cap_now
            flat = rid[:, None] * float(M) + slot_iota  # [R, M]
            rank = jnp.zeros(alive.shape, jnp.float32)
            for br in range(block_r):
                cre_b = sel_grow(creation, br)[:, None, :]
                flat_b = sel_grow(flat, br)[:, None, :]
                idle_b = sel_grow(idle_now, br)[:, None, :]
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

            def drain(_, dcarry):
                alive, creation, busy, acc, qt, qw, qc = dcarry
                any_idle, first_best, any_free, first_free, n_alive = routing(
                    alive, creation, busy, t_new
                )
                cluster = group_sum(alive.sum(axis=1))
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
                neg_col = jnp.full((R, 1), NEG, qt.dtype)
                shift = lambda qx: jnp.where(
                    serve[:, None],
                    jnp.concatenate([qx[:, 1:], neg_col], axis=1),
                    qx,
                )
                return (
                    alive,
                    creation,
                    busy,
                    acc + delta,
                    shift(qt),
                    shift(qw),
                    shift(qc),
                )

            alive, creation, busy, acc, qt, qw, qc = jax.lax.fori_loop(
                0, Q, drain, (alive, creation, busy, acc, qt, qw, qc)
            )

        any_idle, first_best, any_free, first_free, n_alive = routing(
            alive, creation, busy, t_new
        )
        cluster = group_sum(alive.sum(axis=1))
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
            # Exp(crash_rate) lifetime stamped at cold start; warm hits
            # keep the instance's old doom (no reliability layer here —
            # interrupted = the serving instance dies mid-service)
            crash_i = crash_u[:, i]
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
        peak = jnp.maximum(peak, group_sum(alive.sum(axis=1)))
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

    alive0 = jnp.zeros((R, M), jnp.float32)
    frozen = jnp.full((R, M), NEG, jnp.float32)
    t0 = jnp.zeros((R,), jnp.float32)
    acc_cols = FLEET_ACC_COLS + (FAULT_COLS if crashes or cap_steps else 0)
    acc0 = jnp.zeros((R, acc_cols), jnp.float32)
    peak0 = jnp.zeros((R,), jnp.float32)
    if Q:
        qneg = jnp.full((R, Q), NEG, jnp.float32)
        out = jax.lax.fori_loop(
            0, K, step, (alive0, frozen, frozen, t0, acc0, peak0, qneg, qneg, qneg)
        )
    elif crashes:
        doom0 = jnp.full((R, M), jnp.inf, jnp.float32)
        out = jax.lax.fori_loop(
            0, K, step, (alive0, frozen, frozen, t0, acc0, peak0, doom0)
        )
    else:
        out = jax.lax.fori_loop(
            0, K, step, (alive0, frozen, frozen, t0, acc0, peak0)
        )
    acc, peak = out[4], out[5]
    col_iota = jnp.broadcast_to(
        jnp.arange(acc_cols, dtype=jnp.float32)[None, :],
        (R, acc_cols),
    )
    acc = jnp.where(col_iota == float(FLEET_ACC_COLS - 1), peak[:, None], acc)
    return acc, (out[6] if Q else None)


@functools.lru_cache(maxsize=1)
def _fleet_ref_jit():
    def counted(*args, **kw):
        from repro.core.scenario import TRACE_COUNTS

        TRACE_COUNTS["fleet_block_ref"] += 1
        return fleet_sweep_ref(*args, **kw)

    return jax.jit(
        counted,
        static_argnames=("slots", "queue_depth", "block_r", "prestamped"),
    )


@register_backend("ref", engines=("fleet",))
def _ref_fleet_rows(
    t_exp, limit, ncl, t_end, skip, dts, fids, warms, colds,
    *, slots, queue_depth, prestamped, block_k,
    crash_rate=None, crash_u=None, cap_edges=None, cap_values=None,
):
    """The fleet launcher's ``ref`` mirror: no chunk padding needed — the
    jitted mirror consumes the merged rows directly.  Returns
    ``(acc[C, cols], qleft[C])`` like the Pallas launcher."""
    del block_k
    fault_kw = {}
    if crash_u is not None:
        fault_kw["crash_rate"] = jnp.asarray(crash_rate, jnp.float32)
        fault_kw["crash_u"] = jnp.asarray(crash_u, jnp.float32)
    if cap_values is not None:
        fault_kw["cap_edges"] = jnp.asarray(cap_edges, jnp.float32)
        fault_kw["cap_values"] = jnp.asarray(cap_values, jnp.float32)
    acc, qt = _fleet_ref_jit()(
        jnp.asarray(t_exp, jnp.float32),
        jnp.asarray(limit, jnp.float32),
        jnp.asarray(ncl, jnp.float32),
        jnp.asarray(t_end, jnp.float32),
        jnp.asarray(skip, jnp.float32),
        jnp.asarray(dts, jnp.float32),
        jnp.asarray(fids, jnp.float32),
        jnp.asarray(warms, jnp.float32),
        jnp.asarray(colds, jnp.float32),
        slots=slots,
        queue_depth=queue_depth,
        prestamped=prestamped,
        **fault_kw,
    )
    C = acc.shape[0]
    if qt is None:
        qleft = jnp.zeros((C,), jnp.float32)
    else:
        qleft = (qt > NEG * 0.5).sum(axis=1).astype(jnp.float32)
    return acc, qleft


def faas_par_sweep_ref(
    t_exp,  # f32 [R]
    dts,
    warms,
    colds,
    *,
    t_end,
    skip,
    max_concurrency,
    concurrency: int,
    slots: int,
    prestamped: bool = False,
):
    """f32 jnp mirror of ``par_sweep_pallas`` — the par platform's
    ``finish[M, c]`` event loop from an empty pool, same lane-padded slot
    layout (``Mp = ceil(M/LANE)·LANE`` padded slots masked out of the
    free-slot search), same arithmetic order and tie-breaks."""
    from repro.kernels.faas_event_step import LANE, PAR_ACC_COLS

    R, K = dts.shape
    c = concurrency
    Mp = -(-slots // LANE) * LANE
    t_exp = jnp.broadcast_to(jnp.asarray(t_exp, jnp.float32), (R,))
    t_end = jnp.broadcast_to(jnp.asarray(t_end, jnp.float32), (R,))
    skip = jnp.broadcast_to(jnp.asarray(skip, jnp.float32), (R,))
    slot_iota = jnp.broadcast_to(
        jnp.arange(Mp, dtype=jnp.float32)[None, :], (R, Mp)
    )
    real = slot_iota < slots
    sub_iota = jnp.broadcast_to(
        jnp.arange(c, dtype=jnp.float32)[None, :], (R, c)
    )

    def step(i, carry):
        alive, creation, finish, t, acc = carry
        t_new = dts[:, i] if prestamped else t + dts[:, i]
        busy = finish.max(axis=1)
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
        flight_t = jnp.clip(
            jnp.minimum(finish, hi[:, None, None]) - lo[:, None, None], 0.0, None
        )
        flight_sum = (flight_t * alive[:, None, :]).sum(axis=2).sum(axis=1)
        expired = (alive > 0) & (expire <= t_new[:, None])
        alive = jnp.where(expired, 0.0, alive)
        in_flight = (finish > t_new[:, None, None]).sum(axis=1)
        has_cap = (alive > 0) & (in_flight < c)
        best = jnp.max(jnp.where(has_cap, creation, NEG), axis=1)
        any_cap = best > NEG * 0.5
        is_best = has_cap & (creation >= best[:, None]) & any_cap[:, None]
        first_best = jnp.min(jnp.where(is_best, slot_iota, 1e9), axis=1)
        free = (alive <= 0) & real
        any_free = free.any(axis=1)
        first_free = jnp.min(jnp.where(free, slot_iota, 1e9), axis=1)
        n_alive = alive.sum(axis=1)
        active = t_new <= t_end
        counted = t_new > skip
        can_cold = (~any_cap) & (n_alive < max_concurrency) & any_free
        overflow = (~any_cap) & (n_alive < max_concurrency) & (~any_free) & active
        is_warm = any_cap & active
        is_cold = can_cold & active
        is_reject = (~any_cap) & (~can_cold) & active
        chosen = jnp.where(is_warm, first_best, first_free)
        service = jnp.where(is_warm, warms[:, i], colds[:, i])
        assign = is_warm | is_cold
        sel = (slot_iota == chosen[:, None]) & assign[:, None]
        chosen_fin = jnp.where(sel[:, None, :], finish, 0.0).sum(axis=2)
        sub_free = chosen_fin <= t_new[:, None]
        first_sub = jnp.min(jnp.where(sub_free, sub_iota, 1e9), axis=1)
        wipe = sel & is_cold[:, None]
        finish = jnp.where(wipe[:, None, :], NEG, finish)
        set3 = sel[:, None, :] & (sub_iota == first_sub[:, None])[:, :, None]
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
                jnp.where(is_cold & cc, colds[:, i], 0.0),
                jnp.where(is_warm & cc, warms[:, i], 0.0),
                overflow.astype(jnp.float32),
                flight_sum,
            ],
            axis=1,
        )
        return alive, creation, finish, t_new, acc + delta

    alive0 = jnp.zeros((R, Mp), jnp.float32)
    creation0 = jnp.full((R, Mp), NEG, jnp.float32)
    finish0 = jnp.full((R, c, Mp), NEG, jnp.float32)
    t0 = jnp.zeros((R,), jnp.float32)
    acc0 = jnp.zeros((R, PAR_ACC_COLS), jnp.float32)
    out = jax.lax.fori_loop(0, K, step, (alive0, creation0, finish0, t0, acc0))
    return out[4]


@functools.lru_cache(maxsize=1)
def _par_ref_jit():
    def counted(*args, **kw):
        from repro.core.scenario import TRACE_COUNTS

        TRACE_COUNTS["par_block_ref"] += 1
        return faas_par_sweep_ref(*args, **kw)

    return jax.jit(
        counted,
        static_argnames=(
            "max_concurrency",
            "concurrency",
            "slots",
            "prestamped",
        ),
    )


@register_backend("ref", engines=("par",))
def _ref_par_rows(t_exp, t_end, skip, dts, warms, colds, *, block_k, **kw):
    """The par engine's ``ref`` row launcher — the jitted par mirror."""
    del block_k
    return _par_ref_jit()(
        t_exp, dts, warms, colds, t_end=t_end, skip=skip, **kw
    )


def faas_block_step_ref(
    alive, creation, busy, t0, dts, warms, colds, *, t_exp, max_concurrency
):
    """Legacy scalar-threshold entry point (no window masking) — mirrors
    ``faas_block_step_pallas``."""
    R = alive.shape[0]
    return faas_sweep_ref(
        alive,
        creation,
        busy,
        t0,
        jnp.full((R,), t_exp, jnp.float32),
        dts,
        warms,
        colds,
        max_concurrency=max_concurrency,
    )
