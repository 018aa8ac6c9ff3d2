"""TPE surrogate math as XLA-compiled array kernels.

ref mechanism: src/metaopt/algo/tpe.py (SURVEY.md §2.3 [HIGH]): observations
split at the γ-quantile into good/bad sets; per-dimension adaptive-bandwidth
Parzen estimators l(x) and g(x); candidates drawn from l and ranked by
EI ∝ l(x)/g(x). The reference evaluates these densities in Python/numpy per
suggest call; here the density evaluation — the O(candidates × observations ×
dims) part that grows with trial count — is a single jitted kernel over
[0,1]-cube arrays, with observation counts padded to powers of two so XLA
compiles at most O(log n) variants over an experiment's lifetime (this is
what keeps suggest() latency flat past 10k trials, per BASELINE.md).

Everything here is pure and shape-explicit; host-side control plane lives in
:mod:`metaopt_tpu.algo.tpe`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_SQRT2 = 1.4142135623730951


def pad_pow2(n: int, minimum: int = 8) -> int:
    """Padded buffer size ≥ max(n, minimum): powers of two up to 4096,
    then 4096-step multiples.

    Doubling forever wastes up to ~2× FLOPs at ANY scale; stepping by 4096
    past that point bounds the waste by 4096/n (still ~2× just past the
    4096 boundary, shrinking as n grows — <20% by 20k observations) while
    keeping recompiles to O(n/4096) large-n variants (a 100k-trial sweep
    compiles ~25, each reused for 4096 observations).
    """
    p = minimum
    while p < n and p < 4096:
        p *= 2
    if p >= n:
        return p
    return ((n + 4095) // 4096) * 4096


def adaptive_bandwidths(sorted_mu: np.ndarray) -> np.ndarray:
    """Per-component sigmas for a 1-D Parzen mixture on [0, 1].

    Classic adaptive-Parzen rule: each point's sigma is the larger of the
    gaps to its sorted neighbours (edge points use the gap to the domain
    bound), clipped to [1/min(100, n+1), 1]. Host-side numpy — O(n) after the
    caller's sort, negligible next to density evaluation.
    """
    n = len(sorted_mu)
    if n == 0:
        return np.zeros(0)
    if n == 1:
        return np.ones(1)
    ext = np.concatenate([[0.0], sorted_mu, [1.0]])
    left = sorted_mu - ext[:-2]
    right = ext[2:] - sorted_mu
    sig = np.maximum(left, right)
    sig_min = 1.0 / min(100.0, n + 1.0)
    return np.clip(sig, sig_min, 1.0)


def _truncnorm_mixture_logpdf_1d(
    x: jnp.ndarray,      # (C,) evaluation points in [0,1]
    mu: jnp.ndarray,     # (N,) component means
    sigma: jnp.ndarray,  # (N,) component sigmas (>0 even for padding)
    logw: jnp.ndarray,   # (N,) log mixture weights (-inf for padding)
) -> jnp.ndarray:        # (C,)
    """log pdf of a weighted mixture of [0,1]-truncated Gaussians."""
    z = (x[:, None] - mu[None, :]) / sigma[None, :]
    log_phi = -0.5 * z * z - 0.5 * jnp.log(2 * jnp.pi) - jnp.log(sigma[None, :])
    # truncation mass on [0,1] per component
    a = jax.scipy.special.ndtr((1.0 - mu) / sigma)
    b = jax.scipy.special.ndtr((0.0 - mu) / sigma)
    log_mass = jnp.log(jnp.clip(a - b, 1e-12, 1.0))
    return jax.scipy.special.logsumexp(
        log_phi - log_mass[None, :] + logw[None, :], axis=1
    )


#: vmap over dimensions: x (C,d), mu (N,d), sigma (N,d), logw (N,d)
#: (weights are per-dim because adaptive bandwidths sort components per dim)
_mixture_logpdf = jax.vmap(
    _truncnorm_mixture_logpdf_1d, in_axes=(1, 1, 1, 1), out_axes=1
)


# mtpu: hotpath
@functools.partial(jax.jit, static_argnames=())
def ei_scores(
    cand: jnp.ndarray,          # (C, d) candidates in the unit cube
    good_mu: jnp.ndarray,       # (Ng, d)
    good_sigma: jnp.ndarray,    # (Ng, d)
    good_logw: jnp.ndarray,     # (Ng, d)
    bad_mu: jnp.ndarray,        # (Nb, d)
    bad_sigma: jnp.ndarray,     # (Nb, d)
    bad_logw: jnp.ndarray,      # (Nb, d)
    cont_mask: jnp.ndarray,     # (d,) 1.0 for continuous cols, 0.0 for categorical
    cand_cat_idx: jnp.ndarray,  # (C, d) int32 category index (0 for cont cols)
    good_cat_logp: jnp.ndarray, # (d, K) per-dim category log-probs under l
    bad_cat_logp: jnp.ndarray,  # (d, K) per-dim category log-probs under g
) -> jnp.ndarray:               # (C,) EI score = log l(x) - log g(x)
    """Expected-improvement ranking for TPE: log l(x) − log g(x).

    Continuous columns use truncated-Gaussian Parzen mixtures; categorical
    columns use re-weighted category frequency tables (the reference's
    mechanism for categorical dims). One fused kernel — XLA maps the
    (C × N × d) inner product onto the VPU and fuses the masked reduction.
    """
    log_l_cont = _mixture_logpdf(cand, good_mu, good_sigma, good_logw)   # (C, d)
    log_g_cont = _mixture_logpdf(cand, bad_mu, bad_sigma, bad_logw)     # (C, d)

    d_idx = jnp.arange(cand.shape[1])[None, :]                           # (1, d)
    log_l_cat = good_cat_logp[d_idx, cand_cat_idx]                       # (C, d)
    log_g_cat = bad_cat_logp[d_idx, cand_cat_idx]                        # (C, d)

    log_l = jnp.where(cont_mask[None, :] > 0, log_l_cont, log_l_cat)
    log_g = jnp.where(cont_mask[None, :] > 0, log_g_cont, log_g_cat)
    return jnp.sum(log_l - log_g, axis=1)


# ---------------------------------------------------------------------------
# Fully fused suggest kernel
# ---------------------------------------------------------------------------
# The reference recomputes the whole split/sort/fit/sample/score pipeline in
# Python+numpy per suggest() call. Here the entire pipeline is ONE jitted
# program over padded device-resident buffers: γ-split by rank, per-dim sort,
# adaptive bandwidths, recency weights, categorical frequency tables,
# mixture sampling, and EI ranking — no host round-trips, no per-dim Python
# loops. Padding to powers of two keeps the compile count at O(log n) over an
# experiment's lifetime.

_NEG_INF = -jnp.inf
_BIG = 1e9


def _recency_weights(n, idx, full_weight_num, equal_weight: bool):
    """Observation-order weights (lineage forgetting ramp), device-side.

    Matches the host `_weights`: newest ``full_weight_num`` points get weight
    1.0; older points ramp linearly from 1/n up (numpy ``linspace(1/n, 1,
    n - fwn)`` semantics, including the single-element case).
    """
    if equal_weight:
        return jnp.ones_like(idx, dtype=jnp.float32)
    m = n - full_weight_num                      # number of ramped (old) points
    denom = jnp.maximum(m - 1, 1).astype(jnp.float32)
    lo = 1.0 / jnp.maximum(n, 1).astype(jnp.float32)
    ramp = lo + idx.astype(jnp.float32) * (1.0 - lo) / denom
    ramp = jnp.where(m == 1, lo, ramp)           # linspace(1/n, 1, 1) == [1/n]
    w = jnp.where(idx >= m, 1.0, ramp)
    return jnp.where(n <= full_weight_num, 1.0, w)


def _fit_set_device(X, w_sel, count, prior_weight):
    """Per-dim sorted Parzen components for one (masked) observation subset.

    X: (N, d) unit-cube observations (full buffer); w_sel: (N,) recency
    weights, 0.0 outside the subset; count: subset size (traced). Returns
    mu/sigma/logw of shape (N, d) with the prior pseudo-component at row
    ``count`` and -inf log-weight padding elsewhere.
    """
    npad, d = X.shape
    row = jnp.arange(npad)[:, None]                              # (N, 1)
    in_set = w_sel > 0.0

    xg = jnp.where(in_set[:, None], X, _BIG)
    sort_idx = jnp.argsort(xg, axis=0)                           # (N, d)
    xs = jnp.take_along_axis(xg, sort_idx, axis=0)
    ws = jnp.take_along_axis(
        jnp.broadcast_to(w_sel[:, None], (npad, d)), sort_idx, axis=0
    )

    valid = row < count
    prev = jnp.concatenate([jnp.zeros((1, d)), xs[:-1]], axis=0)
    nxt = jnp.concatenate([xs[1:], jnp.full((1, d), _BIG)], axis=0)
    left = xs - prev
    right = jnp.where(row == count - 1, 1.0 - xs, nxt - xs)
    sig = jnp.maximum(left, right)
    sig_min = 1.0 / jnp.minimum(100.0, count.astype(jnp.float32) + 1.0)
    sig = jnp.clip(sig, sig_min, 1.0)
    sig = jnp.where(count == 1, 1.0, sig)        # host rule: single point → 1.0

    is_prior = row == count
    mu = jnp.where(valid, xs, 0.5)
    sigma = jnp.where(valid, sig, 1.0)
    logw = jnp.where(valid, jnp.log(jnp.clip(ws, 1e-12, None)), _NEG_INF)
    logw = jnp.where(is_prior, jnp.log(jnp.maximum(prior_weight, 1e-12)), logw)
    return mu, sigma, logw


def _categorical_cdf(key, logits, shape):
    """Categorical draws: ONE uniform per slot + an inverse-CDF sweep.

    Drop-in for ``jax.random.categorical(key, logits, shape=shape)`` on
    the suggest hot path (same distribution, different bit mapping —
    the Gumbel-max trick burns K gumbels PER draw, two transcendentals
    each, which profiled as the single largest cost of a suggest launch
    on CPU: ~90 us/experiment of a ~250 us body). Here the CDF costs one
    softmax+cumsum over the logits (constant in the draw count) and each
    draw is one uniform plus K compares.

    Selection is "first k with cdf[k] >= u": a zero-probability category
    (-inf logit) has cdf[k] == cdf[k-1] and can never satisfy
    cdf[k] >= u > cdf[k-1], so dead/padded components are never drawn
    (the clamp only guards the u ~ 1.0 rounding edge).
    """
    cdf = jnp.cumsum(jax.nn.softmax(logits, axis=-1), axis=-1)
    u = jax.random.uniform(key, shape, dtype=cdf.dtype)
    draw = jnp.sum(u[..., None] > cdf, axis=-1)
    return jnp.minimum(draw, logits.shape[-1] - 1).astype(jnp.int32)


def _cat_tables_device(X, w_sel, n_choices, prior_weight, kmax: int):
    """Re-weighted category frequency tables, (d, kmax) log-probs."""
    npad, d = X.shape
    k = jnp.maximum(n_choices, 1)                                # (d,)
    cat_idx = jnp.minimum((X * k[None, :]).astype(jnp.int32),
                          (k - 1)[None, :])                      # (N, d)
    col = jnp.arange(kmax)[None, :]                              # (1, K)
    base = jnp.where(col < k[:, None], prior_weight, 0.0)        # (d, K)

    def scatter_one(ci, base_row):
        return base_row.at[ci].add(w_sel)

    counts = jax.vmap(scatter_one, in_axes=(1, 0))(cat_idx, base)  # (d, K)
    probs = counts / jnp.clip(counts.sum(axis=1, keepdims=True), 1e-12, None)
    return jnp.log(jnp.clip(probs, 1e-12, None))


def _tpe_suggest_body(
    X,                   # (N, d) unit-cube observations, padded (N ≥ n+1)
    y,                   # (N,) objectives, +inf padding
    n,                   # scalar int32: live observation count
    count,               # scalar int32: PRNG stream position (fold_in on device)
    base_key,            # PRNG key (created once per algorithm instance)
    n_choices,           # (d,) int32: categories per dim (≤1 for continuous)
    cont_mask,           # (d,) bool: True for continuous dims
    gamma,               # scalar: good-set quantile
    prior_weight,        # scalar: prior pseudo-count / pseudo-component weight
    full_weight_num,     # scalar int32: recency ramp cutoff
    n_prior=0,           # scalar int32: rows 0..n_prior-1 are transfer priors
    transfer_discount=1.0,  # scalar: weight multiplier on those rows
    *,
    n_cand: int,
    n_out: int,
    kmax: int,
    equal_weight: bool,
    n_good_pad: int = 0,
    n_bad_pad: int = 0,
    n_pools: int = 1,
):
    """Whole suggest pools in ONE device program + ONE host readback.

    Scores ``n_cand`` candidates per output slot against a shared l/g fit
    and returns the winners, shape (n_pools * n_out, d) — ``n_pools``
    independent prefetch pools, each keyed ``fold_in(base_key, count + p)``
    so pool ``p`` draws the EXACT stream a separate launch at stream
    position ``count + p`` would (counter-based threefry: no state carries
    between pools). One call serves every pool: a blocking device→host
    readback has a fixed cost whatever the payload size.

    The good/bad sets are COMPACTED before fitting: the γ-split selects
    ``n_below`` good rows out of n, so density evaluation runs over
    ``n_good_pad``/``n_bad_pad`` components (pads of n_below+1 and
    n−n_below+1, computed host-side from the live count with the same
    formula as the in-kernel split) instead of 2× the full buffer — at
    γ=0.25 that cuts the O(C·N·d) inner product roughly in half. Pass
    0 (default) to fit over the full buffer width.
    """
    npad, d = X.shape
    if not n_good_pad:
        n_good_pad = npad
    if not n_bad_pad:
        n_bad_pad = npad
    idx = jnp.arange(npad)

    # γ-split by objective rank (padding sorts last via +inf)
    order = jnp.argsort(jnp.where(idx < n, y, jnp.inf))
    n_below = jnp.minimum(
        jnp.maximum(1, jnp.ceil(gamma * n).astype(jnp.int32)),
        jnp.maximum(n, 1),
    )
    # safety clamp: the caller sized n_good_pad from the same formula on the
    # host; never let a rounding divergence index past the prior row
    n_below = jnp.minimum(n_below, n_good_pad - 1)
    w_obs = _recency_weights(n, idx, full_weight_num, equal_weight)
    # transfer priors (EVC warm-start) occupy the OLDEST rows; their
    # evidence is discounted so locally-measured points dominate the fit
    # as soon as they exist. Traced scalars: no new compile variants.
    w_obs = w_obs * jnp.where(idx < n_prior, transfer_discount, 1.0)
    ng = jnp.minimum(n_below, n)
    nb = jnp.maximum(n - n_below, 0)

    # compact gather: good rows are order[0:n_below], bad rows follow
    gpos = jnp.arange(n_good_pad)
    gsel = order[jnp.minimum(gpos, npad - 1)]
    w_good = jnp.where(gpos < ng, w_obs[gsel], 0.0)
    Xg = X[gsel]
    bpos = n_below + jnp.arange(n_bad_pad)
    bsel = order[jnp.minimum(bpos, npad - 1)]
    w_bad = jnp.where(bpos < n, w_obs[bsel], 0.0)
    Xb = X[bsel]

    g_mu, g_sig, g_logw = _fit_set_device(Xg, w_good, ng, prior_weight)
    b_mu, b_sig, b_logw = _fit_set_device(Xb, w_bad, nb, prior_weight)
    g_cat = _cat_tables_device(Xg, w_good, n_choices, prior_weight, kmax)
    b_cat = _cat_tables_device(Xb, w_bad, n_choices, prior_weight, kmax)

    # ---- per pool: sample n_out slots of n_cand candidates from l ----
    dim_idx = jnp.arange(d)[None, :]                             # (1, d)
    C = n_out * n_cand
    k = jnp.maximum(n_choices, 1)
    cat_logits = jnp.where(jnp.arange(kmax)[None, :] < k[:, None],
                           g_cat, _NEG_INF)                      # (d, K)

    outs = []
    for p in range(n_pools):
        key = jax.random.fold_in(base_key, count + p)
        k_comp, k_draw, k_redraw, k_cat = jax.random.split(key, 4)

        comp = _categorical_cdf(k_comp, g_logw.T, (C, d))
        mu_c = g_mu[comp, dim_idx]
        sig_c = g_sig[comp, dim_idx]
        draws = mu_c + sig_c * jax.random.normal(k_draw, (C, d))
        redraw = mu_c + sig_c * jax.random.normal(k_redraw, (C, d))
        oob = (draws < 0.0) | (draws > 1.0)
        draws = jnp.clip(jnp.where(oob, redraw, draws), 1e-6, 1.0 - 1e-6)

        cats = _categorical_cdf(k_cat, cat_logits, (C, d))
        cat_vals = (cats.astype(jnp.float32) + 0.5) / k[None, :]

        cand = jnp.where(cont_mask[None, :], draws, cat_vals)    # (C, d)
        cand_cat = jnp.minimum((cand * k[None, :]).astype(jnp.int32),
                               (k - 1)[None, :])

        # ---- EI ranking: log l(x) - log g(x) ----
        log_l = _mixture_logpdf(cand, g_mu, g_sig, g_logw)
        log_g = _mixture_logpdf(cand, b_mu, b_sig, b_logw)
        log_l = jnp.where(cont_mask[None, :], log_l,
                          g_cat[dim_idx, cand_cat])
        log_g = jnp.where(cont_mask[None, :], log_g,
                          b_cat[dim_idx, cand_cat])
        scores = jnp.sum(log_l - log_g, axis=1).reshape(n_out, n_cand)
        winners = jnp.argmax(scores, axis=1)                     # (n_out,)
        outs.append(
            cand.reshape(n_out, n_cand, d)[jnp.arange(n_out), winners]
        )
    return outs[0] if n_pools == 1 else jnp.concatenate(outs, axis=0)


#: the per-experiment entry point: ONE experiment, one jitted program.
#: The traced pipeline lives in ``_tpe_suggest_body`` so the fleet kernel
#: below vmaps the IDENTICAL computation — bit-identity of fused vs
#: per-experiment suggestions reduces to "same body, same inputs".
tpe_suggest_fused = functools.partial(
    jax.jit,
    static_argnames=(
        "n_cand", "n_out", "kmax", "equal_weight",
        "n_good_pad", "n_bad_pad", "n_pools",
    ),
)(_tpe_suggest_body)


def _stk(col):
    """Column-stack a fleet input inside the trace.

    Each column arrives either already stacked (a (B, ...) array — the
    test-friendly form) or as a TUPLE of B per-experiment leaves — the
    bucket-native form the fuser passes. Tuples are stacked HERE, inside
    the jitted program: the stack compiles into the launch (one dispatch
    for the whole bucket instead of ~2 dispatched host ops per column
    per member, which measured 14 ms of a 32 ms sweep at B=16), and
    device-resident buffers are stacked device-side, never touching the
    host. The tuple length is part of the jit cache key, which is fine:
    it equals the pow2-padded bucket size the static key already pins.
    """
    return jnp.stack(col) if isinstance(col, (tuple, list)) else col


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_cand", "n_out", "kmax", "equal_weight",
        "n_good_pad", "n_bad_pad", "n_pools",
    ),
)
def tpe_suggest_fleet(
    X,                   # (B, N, d) stacked — or a B-tuple of (N, d)
    y,                   # (B, N) objectives, +inf padding
    n,                   # (B,) int32 live counts (may differ within a pad)
    count,               # (B,) int32 PRNG stream positions
    base_key,            # (B, key) per-experiment base keys
    n_choices,           # (B, d) int32
    cont_mask,           # (B, d) bool
    gamma,               # (B,) float32
    prior_weight,        # (B,) float32
    full_weight_num,     # (B,) float32
    n_prior,             # (B,) int32
    transfer_discount,   # (B,) float32
    *,
    n_cand: int,
    n_out: int,
    kmax: int,
    equal_weight: bool,
    n_good_pad: int = 0,
    n_bad_pad: int = 0,
    n_pools: int = 1,
):
    """``tpe_suggest_fused`` for a BUCKET of experiments in ONE launch.

    vmaps ``_tpe_suggest_body`` over a leading experiment axis: every
    per-experiment quantity (buffer, live count, stream position, space
    encoding, hyperparameters) is stacked and traced, while the bucket
    key's statics (pads, candidate/pool widths, kmax, equal_weight) are
    uniform across members — that is exactly what makes two experiments
    bucket-compatible (coord/fuser.py). Every column accepts either the
    stacked (B, ...) array or a B-tuple of per-experiment leaves, which
    is stacked in-trace (see ``_stk``). Row b of the result is bitwise
    the array ``tpe_suggest_fused`` would return for experiment b alone:
    the body is the same traced code, reductions keep their per-row
    order under the batch dim, and the PRNG is counter-based per
    experiment (fold_in of b's own key — nothing crosses the stack
    axis). Returns (B, n_pools * n_out, d).
    """
    body = functools.partial(
        _tpe_suggest_body,
        n_cand=n_cand, n_out=n_out, kmax=kmax, equal_weight=equal_weight,
        n_good_pad=n_good_pad, n_bad_pad=n_bad_pad, n_pools=n_pools,
    )
    return jax.vmap(body)(
        _stk(X), _stk(y), _stk(n), _stk(count), _stk(base_key),
        _stk(n_choices), _stk(cont_mask), _stk(gamma), _stk(prior_weight),
        _stk(full_weight_num), _stk(n_prior), _stk(transfer_discount),
    )


def split_pads(n: int, gamma: float) -> tuple:
    """Static (n_good_pad, n_bad_pad) for a live count, mirroring the
    in-kernel γ-split so the compacted fit always has room for the subset
    plus its prior pseudo-component row. float32 math on purpose — it must
    round exactly like the traced ``ceil(gamma * n)`` inside the kernel."""
    n = int(n)
    n_below = int(np.ceil(np.float32(gamma) * np.float32(n)))
    n_below = min(max(1, n_below), max(n, 1))
    return pad_pow2(n_below + 1), pad_pow2(max(n - n_below, 0) + 1)
