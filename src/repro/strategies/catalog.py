"""All 26 merge strategies (paper Appendix B), as pure JAX n-ary functions.

Conventions: `s` is the stacked contributions [k, ...]; `b` the base
parameters (zeros for raw tensor audits); tau = s - b the task vectors
(paper §2.2). Where the source publication leaves implementation freedom
(derived/community strategies), parameter choices are pinned so the raw
Phase-1 algebraic profile matches the paper's Table 3 (asserted exactly
by tests/test_strategies_audit.py):

  name                     C A I   mechanism that breaks the failed axiom
  ada_merging              P F P   inverse-variance weights (nonlinear avg)
  adarank                  P F F   SVD rank truncation of mean tau
  dam                      P F P   magnitude-weighted averaging
  dare                     F F F   unseeded Bernoulli mask + rescale
  dare_ties                F F F   DARE mask + sign election
  della                    F F F   magnitude-ranked stochastic drop
  dual_projection          P F P   projection onto mean direction
  emr                      P F F   elect-mask-rescale + trim
  evolutionary_merge       F F F   population search, unnormalised weights
  fisher_merge             P F P   squared-magnitude (proxy) Fisher weights
  genetic_merge            P F P   deterministic generational coeff search
  led_merge                P F P   largest-element-dominance softmax blend
  linear                   P F P   interpolation (t=0.5)
  model_breadcrumbs        P F F   top+bottom magnitude masking
  negative_merge           P F F   subtractive (unlearning) merge
  regression_mean          P F P   row-energy regression weights
  representation_surgery   P F P   column-norm alignment then mean
  safe_merge               P F P   pooled 6-sigma clip then mean
  slerp                    P F P   spherical interpolation (t=0.5)
  split_unlearn_merge      P F F   sign-split + sqrt(k) variance rescale
  star                     P F F   spectral truncate-and-rescale
  svd_knot_tying           F F P   first-contribution SVD basis
  task_arithmetic          P P F   b + sum(tau)  (lambda=1)
  ties                     P F F   trim + sign election + disjoint mean
  weight_average           P F P   arithmetic mean
  weight_scope_alignment   P F P   geometric-mean norm re-projection
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.strategies.base import (
    LeafFold, leafwise, register, run_fold, Strategy)
from repro.strategies.select import exact_quantile_rows

EPS = 1e-12


def _fl(x):
    """Flatten all but the leading (k) axis."""
    return x.reshape(x.shape[0], -1)


def _norms(t):
    return jnp.sqrt(jnp.sum(_fl(t) ** 2, axis=1)) + EPS


def _as2d(x):
    if x.ndim >= 2:
        return x.reshape(x.shape[0], -1), x.shape
    return x.reshape(1, -1), x.shape


# ---------------------------------------------------------------- linear ---
# The linear family is *algebraically incremental*: each strategy's
# canonical per-leaf math is an explicit sequential float32 LeafFold
# (init/step/finalize) rather than jnp.mean/jnp.sum, because XLA
# reductions reassociate — sum(s, axis=0) is NOT bitwise equal to a
# left fold on this platform, and the engine's fold_update resumption
# must be bit-equal to the full recompute. leaf_fn and fold_update both
# drive the same fold via `run_fold`, so equality holds by construction
# (audited for every prefix length in tests/test_strategies_audit.py).


def _cast(out, dtype):
    """Accumulation is float32; cast back for floating inputs (integer
    inputs keep the float32 result, matching jnp.mean semantics)."""
    return out.astype(dtype) if jnp.issubdtype(dtype, jnp.floating) else out


def _sum_init(x0, b, **kw):
    return x0


def _sum_step(acc, x, b, **kw):
    return acc + x


def _mean_fin(acc, k, b, dtype, **kw):
    return _cast(acc / k, dtype)


def _tau_init(x0, b, **kw):
    return x0 - jnp.asarray(b, jnp.float32)


def _tau_step(acc, x, b, **kw):
    return acc + (x - jnp.asarray(b, jnp.float32))


def _ta_fin(acc, k, b, dtype, lam=1.0, **kw):
    return _cast(jnp.asarray(b, jnp.float32) + lam * acc, dtype)


def _neg_fin(acc, k, b, dtype, lam=0.5, **kw):
    return _cast(jnp.asarray(b, jnp.float32) - lam * (acc / k), dtype)


MEAN_FOLD = LeafFold(_sum_init, _sum_step, _mean_fin)
# linear interpolates at k == 2 (a different formula), so its fold is
# only the canonical computation from k == 3 up — the engine must not
# resume from (or finalize at) any shorter prefix.
LINEAR_FOLD = LeafFold(_sum_init, _sum_step, _mean_fin, min_k=3)
TASK_ARITH_FOLD = LeafFold(_tau_init, _tau_step, _ta_fin)
NEGATIVE_FOLD = LeafFold(_tau_init, _tau_step, _neg_fin)


def _weight_average(s, b, **kw):
    return run_fold(MEAN_FOLD, s, b, **kw)[0]


def _linear(s, b, t=0.5, **kw):
    if s.shape[0] == 2:
        return (1.0 - t) * s[0] + t * s[1]
    return run_fold(LINEAR_FOLD, s, b, t=t, **kw)[0]


def _task_arithmetic(s, b, lam=1.0, **kw):
    return run_fold(TASK_ARITH_FOLD, s, b, lam=lam, **kw)[0]


def _negative_merge(s, b, lam=0.5, **kw):
    return run_fold(NEGATIVE_FOLD, s, b, lam=lam, **kw)[0]


def _fisher_merge(s, b, eps=1e-8, **kw):
    f = s * s + eps
    return jnp.sum(f * s, axis=0) / jnp.sum(f, axis=0)


def _dam(s, b, **kw):
    tau = s - b
    w = _norms(tau)
    w = w / jnp.sum(w)
    shape = (-1,) + (1,) * (s.ndim - 1)
    return b + jnp.sum(w.reshape(shape) * tau, axis=0)


def _ada_merging(s, b, eps=1e-8, **kw):
    tau = s - b
    var = jnp.var(_fl(tau), axis=1) + eps
    w = (1.0 / var) / jnp.sum(1.0 / var)
    shape = (-1,) + (1,) * (s.ndim - 1)
    return b + jnp.sum(w.reshape(shape) * tau, axis=0)


def _regression_mean(s, b, eps=1e-8, **kw):
    if s.ndim == 1:
        return jnp.mean(s, axis=0)
    k = s.shape[0]
    flat = s.reshape(k, s.shape[1], -1)
    w = jnp.mean(flat ** 2, axis=2) + eps          # [k, rows]
    w = w / jnp.sum(w, axis=0, keepdims=True)
    merged = jnp.sum(w[:, :, None] * flat, axis=0)
    return merged.reshape(s.shape[1:])


# ---------------------------------------------------------------- sparse ---


def _ties(s, b, trim=0.2, trim_method="quantile", **kw):
    if trim_method == "histogram":
        return _ties_nd_histogram(s, b, trim)
    return _ties_quantile(s, b, trim, [()])


def _ties_blocked(trim=0.2, trim_method="quantile", **kw):
    """`_ties` block by block, for the executor to bound its working
    set on a large leaf: fn(s, b, blocks), `blocks` rectangular index
    tuples over the leaf's leading axes in row-major order (see
    `core.engine.leading_blocks`; `[()]`: the whole leaf, `_ties`
    itself). None for the histogram trim (whole-leaf only)."""
    if trim_method == "histogram":
        return None
    return lambda s, b, blocks: _ties_quantile(s, b, trim, blocks)


def _ties_quantile(s, b, trim, blocks):
    """TIES with the exact quantile trim. The threshold is one per row
    over the whole leaf; after it every step is elementwise or reduces
    over k alone, so it runs one block at a time with each block's bits
    the whole leaf's."""
    tau = _fl(s - b)
    a = jnp.abs(tau)
    q = exact_quantile_rows(a, trim)
    if len(blocks) == 1:
        return b + _ties_elect(tau, a, q).reshape(s.shape[1:])
    # each step is waited for before the next is dispatched: queued
    # ahead of the device, every block's buffers would be live at once
    q = jax.block_until_ready(q)
    del tau, a
    out = []
    for blk in blocks:
        bb = b[blk]
        tau = _fl(s[(slice(None),) + blk] - bb)
        merged = _ties_elect(tau, jnp.abs(tau), q)
        out.append(jax.block_until_ready(
            (bb + merged.reshape(bb.shape)).reshape(-1)))
    return jnp.concatenate(out).reshape(s.shape[1:])


def _ties_elect(tau, a, q):
    """TIES after the threshold `q` ([k, 1]) on flat task vectors
    `tau` ([k, n]) and their magnitudes `a`: trim, sign election,
    disjoint mean."""
    trimmed = tau * (a >= q).astype(tau.dtype)
    elected = jnp.sign(jnp.sum(trimmed, axis=0, keepdims=True))
    agree = (jnp.sign(trimmed) == elected) & (trimmed != 0)
    agree = agree.astype(tau.dtype)
    cnt = jnp.maximum(jnp.sum(agree, axis=0), 1.0)
    return jnp.sum(trimmed * agree, axis=0) / cnt


def _ties_nd_histogram(s, b, trim, bins=512):
    """Sharding-preserving TIES: NO flatten/reshape (which would force
    GSPMD to all-gather mixed-sharded dims), no global sort. The trim
    threshold comes from an N-D scatter-add histogram; everything else is
    elementwise + axis-0 reductions, so a sharded k-way merge stays
    entirely shard-local apart from the [k, bins] histogram psum."""
    tau = s - b
    a = jnp.abs(tau)
    red_axes = tuple(range(1, tau.ndim))
    amax = jnp.max(a, axis=red_axes, keepdims=True) + 1e-12
    idx = jnp.clip((a / amax * bins).astype(jnp.int32), 0, bins - 1)

    def per_contrib(idx_k):
        return jnp.zeros((bins,), jnp.float32).at[idx_k].add(1.0)

    counts = jax.vmap(per_contrib)(idx)                  # [k, bins]
    n = 1
    for d in tau.shape[1:]:
        n *= d
    cdf = jnp.cumsum(counts, axis=1) / jnp.float32(n)
    bucket = jnp.argmax(cdf >= trim, axis=1).astype(tau.dtype)
    thr = (bucket.reshape((-1,) + (1,) * (tau.ndim - 1)) / bins) * amax
    trimmed = tau * (a >= thr).astype(tau.dtype)
    elected = jnp.sign(jnp.sum(trimmed, axis=0, keepdims=True))
    agree = ((jnp.sign(trimmed) == elected) & (trimmed != 0)).astype(
        tau.dtype)
    cnt = jnp.maximum(jnp.sum(agree, axis=0), 1.0)
    return b + jnp.sum(trimmed * agree, axis=0) / cnt


def _dare(s, b, key, p=0.5, **kw):
    tau = s - b
    mask = jax.random.bernoulli(key, 1.0 - p, tau.shape).astype(tau.dtype)
    return b + jnp.mean(tau * mask / (1.0 - p), axis=0)


def _dare_ties(s, b, key, p=0.5, **kw):
    tau = _fl(s - b)
    mask = jax.random.bernoulli(key, 1.0 - p, tau.shape).astype(tau.dtype)
    kept = tau * mask / (1.0 - p)
    elected = jnp.sign(jnp.sum(kept, axis=0, keepdims=True))
    agree = ((jnp.sign(kept) == elected) & (kept != 0)).astype(tau.dtype)
    cnt = jnp.maximum(jnp.sum(agree, axis=0), 1.0)
    merged = jnp.sum(kept * agree, axis=0) / cnt
    return b + merged.reshape(s.shape[1:])


def _della(s, b, key, p_min=0.2, p_max=0.8, **kw):
    """Magnitude-based sampling: low-|tau| entries drop more often."""
    tau = _fl(s - b)
    r = jnp.argsort(jnp.argsort(jnp.abs(tau), axis=1), axis=1).astype(
        tau.dtype)
    r = r / jnp.maximum(tau.shape[1] - 1, 1)
    p_drop = p_max - (p_max - p_min) * r
    u = jax.random.uniform(key, tau.shape, dtype=tau.dtype)
    keep = (u >= p_drop).astype(tau.dtype)
    kept = tau * keep / jnp.maximum(1.0 - p_drop, 1e-3)
    merged = jnp.mean(kept, axis=0)
    return b + merged.reshape(s.shape[1:])


def _model_breadcrumbs(s, b, beta=0.1, gamma=0.1, **kw):
    tau = _fl(s - b)
    a = jnp.abs(tau)
    qlo = exact_quantile_rows(a, beta)
    qhi = exact_quantile_rows(a, 1.0 - gamma)
    mask = ((a >= qlo) & (a <= qhi)).astype(tau.dtype)
    merged = jnp.mean(tau * mask, axis=0)
    return b + merged.reshape(s.shape[1:])


def _emr(s, b, trim=0.1, **kw):
    tau = _fl(s - b)
    elected = jnp.sign(jnp.sum(tau, axis=0, keepdims=True))
    mask = (jnp.sign(tau) == elected).astype(tau.dtype)
    m = jnp.sum(tau * mask, axis=0) / jnp.maximum(jnp.sum(mask, axis=0), 1.0)
    q = exact_quantile_rows(jnp.abs(m)[None], trim)[0, 0]
    m = m * (jnp.abs(m) >= q)
    rho = jnp.mean(_norms(s - b)) / (jnp.linalg.norm(m) + EPS)
    return b + (rho * m).reshape(s.shape[1:])


def _safe_merge(s, b, k_sigma=6.0, **kw):
    tau = s - b
    mu = jnp.mean(tau)
    sd = jnp.std(tau) + EPS
    clipped = jnp.clip(tau, mu - k_sigma * sd, mu + k_sigma * sd)
    return b + jnp.mean(clipped, axis=0)


def _split_unlearn_merge(s, b, **kw):
    tau = _fl(s - b)
    k = tau.shape[0]
    elected = jnp.sign(jnp.sum(tau, axis=0, keepdims=True))
    agree = (jnp.sign(tau) == elected).astype(tau.dtype)
    kept = jnp.sum(tau * agree, axis=0) / jnp.maximum(
        jnp.sum(agree, axis=0), 1.0)
    # variance-compensation rescale (breaks idempotency: sqrt(k) factor)
    target = jnp.sqrt(float(k)) * jnp.mean(_norms(s - b))
    merged = kept * target / (jnp.linalg.norm(kept) + EPS)
    return b + merged.reshape(s.shape[1:])


def _star(s, b, keep_frac=0.75, **kw):
    tau = jnp.mean(s - b, axis=0)
    if tau.ndim < 2:
        return b + tau
    m2d, shape = tau.reshape(tau.shape[0], -1), tau.shape
    u, sv, vt = jnp.linalg.svd(m2d, full_matrices=False)
    r = max(1, int(jnp.floor(keep_frac * sv.shape[0])))
    kept = sv * (jnp.arange(sv.shape[0]) < r)
    scale = jnp.sum(sv) / (jnp.sum(kept) + EPS)     # preserve nuclear norm
    recon = (u * (kept * scale)) @ vt
    return b + recon.reshape(shape)


# -------------------------------------------------------------- geometry ---


def _slerp(s, b, t=0.5, **kw):
    assert s.shape[0] == 2, "slerp is binary"
    u, v = _fl(s)[0], _fl(s)[1]
    nu, nv = jnp.linalg.norm(u) + EPS, jnp.linalg.norm(v) + EPS
    uh, vh = u / nu, v / nv
    cos = jnp.clip(jnp.dot(uh, vh), -1.0, 1.0)
    omega = jnp.arccos(cos)
    so = jnp.sin(omega)
    w1 = jnp.where(so < 1e-6, 1.0 - t, jnp.sin((1.0 - t) * omega) / so)
    w2 = jnp.where(so < 1e-6, t, jnp.sin(t * omega) / so)
    direction = w1 * uh + w2 * vh
    mag = (1.0 - t) * nu + t * nv
    return (direction * mag).reshape(s.shape[1:])


def _dual_projection(s, b, gamma=0.5, eps=1e-12, **kw):
    tau = _fl(s - b)
    mu = jnp.mean(tau, axis=0)
    denom = jnp.dot(mu, mu) + eps
    proj = (tau @ mu)[:, None] / denom * mu[None, :]
    resid = tau - proj
    merged = jnp.mean(proj + gamma * resid, axis=0)
    return b + merged.reshape(s.shape[1:])


def _svd_knot_tying(s, b, keep_frac=0.5, **kw):
    """Tie later contributions into the FIRST contribution's dominant
    singular subspace; the first's out-of-subspace residual is preserved
    (so f(a, a) = a, but the result depends on which input comes first)."""
    tau = s - b
    k = tau.shape[0]
    if tau.ndim >= 3:                       # [k, rows, cols]
        flat = tau.reshape(k, tau.shape[1], -1)
        u, sv, vt = jnp.linalg.svd(flat[0], full_matrices=False)
        r = max(1, int(jnp.floor(keep_frac * sv.shape[0])))
        ur, vtr = u[:, :r], vt[:r, :]
        coeff = jnp.einsum("ir,krc,jc->kij", ur.T, flat, vtr)   # [k, r, r]
        recon = ur @ jnp.mean(coeff, axis=0) @ vtr
        resid = flat[0] - ur @ (ur.T @ flat[0] @ vtr.T) @ vtr
        return b + (recon + resid).reshape(tau.shape[1:])
    # 1-D: dominant-coordinate mask from the first contribution
    flat = tau.reshape(k, -1)
    a0 = jnp.abs(flat[0])
    mask = (a0 >= jnp.median(a0)).astype(flat.dtype)
    merged = jnp.mean(flat, axis=0) * mask + flat[0] * (1.0 - mask)
    return b + merged.reshape(tau.shape[1:])


def _representation_surgery(s, b, eps=1e-8, **kw):
    if s.ndim < 3:
        n = _norms(s)
        target = jnp.mean(n)
        shape = (-1,) + (1,) * (s.ndim - 1)
        return jnp.mean(s * (target / n).reshape(shape), axis=0)
    flat = s.reshape(s.shape[0], s.shape[1], -1)
    n = jnp.sqrt(jnp.sum(flat ** 2, axis=1)) + eps      # [k, cols]
    target = jnp.mean(n, axis=0, keepdims=True)
    aligned = flat * (target / n)[:, None, :]
    return jnp.mean(aligned, axis=0).reshape(s.shape[1:])


def _weight_scope_alignment(s, b, **kw):
    n = _norms(s)
    gm = jnp.exp(jnp.mean(jnp.log(n)))
    shape = (-1,) + (1,) * (s.ndim - 1)
    dirs = s / n.reshape(shape)
    mean_dir = jnp.mean(dirs, axis=0)
    mean_dir = mean_dir / (jnp.linalg.norm(mean_dir) + EPS)
    return gm * mean_dir


def _led_merge(s, b, beta=5.0, gamma=0.7, **kw):
    tau = s - b
    scale = jnp.mean(jnp.abs(tau)) + EPS
    w = jax.nn.softmax(beta * jnp.abs(tau) / scale, axis=0)
    dom = jnp.sum(w * tau, axis=0)
    return b + gamma * dom + (1.0 - gamma) * jnp.mean(tau, axis=0)


def _adarank(s, b, keep_frac=0.5, **kw):
    tau = jnp.mean(s - b, axis=0)
    if tau.ndim < 2:
        return b + tau
    m2d = tau.reshape(tau.shape[0], -1)
    u, sv, vt = jnp.linalg.svd(m2d, full_matrices=False)
    r = max(1, int(jnp.floor(keep_frac * sv.shape[0])))
    kept = sv * (jnp.arange(sv.shape[0]) < r)
    recon = (u * kept) @ vt
    return b + recon.reshape(tau.shape)


# ---------------------------------------------------------------- search ---


def _evolutionary_merge(s, b, key, pop=16, gens=3, sigma=0.3, **kw):
    """Population search over (unnormalised) mixing weights."""
    tau = _fl(s - b)
    k = tau.shape[0]
    med = jnp.median(tau, axis=0)

    def fitness(w):
        cand = w @ tau                                   # [n]
        return -jnp.sum((cand - med) ** 2)

    best_w = jnp.full((k,), 1.0 / k)
    for g in range(gens):
        key, sub = jax.random.split(key)
        cands = best_w[None, :] + sigma * (0.5 ** g) * jax.random.normal(
            sub, (pop, k), dtype=tau.dtype)
        fits = jax.vmap(fitness)(cands)
        best_w = cands[jnp.argmax(fits)]
    merged = best_w @ tau
    return b + merged.reshape(s.shape[1:])


def _genetic_merge(s, b, grid=11, gens=3, reg=0.05, **kw):
    """Deterministic generational search over a scalar coefficient alpha."""
    tau = _fl(s - b)
    mu = jnp.mean(tau, axis=0)
    med = jnp.median(tau, axis=0)

    def fitness(alpha):
        return -(jnp.sum((alpha * mu - med) ** 2)
                 + reg * (alpha - 1.0) ** 2 * jnp.sum(mu ** 2))

    lo, hi = 0.5, 1.5
    alpha = 1.0
    for g in range(gens):
        cands = jnp.linspace(lo, hi, grid)
        fits = jax.vmap(fitness)(cands)
        alpha = cands[jnp.argmax(fits)]
        span = (hi - lo) / 4.0
        lo, hi = alpha - span, alpha + span
    merged = alpha * mu
    return b + merged.reshape(s.shape[1:])


# ------------------------------------------------------------------ registry


def _reg(name, leaf_fn, *, schema, needs_key=False, stochastic=False,
         binary_only=False, category="linear", whole_model=False,
         elementwise=False, fold=None, blocked=None, **defaults):
    register(Strategy(name=name, fn=leafwise(leaf_fn, needs_key=needs_key),
                      stochastic=stochastic, binary_only=binary_only,
                      category=category, defaults=defaults,
                      leaf_fn=leaf_fn, needs_key=needs_key,
                      whole_model=whole_model, elementwise=elementwise,
                      cfg_schema=dict(schema), fold=fold, blocked=blocked))


# `elementwise`: the leaf function reduces only over the leading k axis
# (no per-leaf norms/quantiles/SVD/shape use), so the engine may fuse
# arbitrarily many leaves into one flattened [k, N] dispatch — same
# per-element arithmetic, byte-identical output.
# `blocked`: the leaf function also runs block by block, bit for bit
# (TIES's quantile trim); the executor uses it, as it uses a `fold`, to
# bound a large leaf's working set. DARE's per-leaf random stream and
# SLERP's whole-leaf norms would change bits under blocks: they, like
# every other strategy without either, run whole-leaf.
# `whole_model`: population-search and SVD-based strategies whose cost
# profile is dominated by per-call factorization/search rather than
# streaming elementwise math; the engine routes them through the legacy
# whole-tree path (and caches one whole-model entry) instead of
# pretending a per-tensor plan buys anything.
# `schema`: the strategy's declared cfg knobs ({name: (type, default)}),
# enforced by repro.api.MergeSpec at spec construction. The declaration
# must mirror the leaf function's keyword signature exactly — names,
# types AND default values — because MergeSpec canonicalizes declared
# defaults into the cache key; tests/test_strategies_audit.py diffs
# every schema against inspect.signature so the two cannot drift.

_reg("weight_average", _weight_average, elementwise=True, schema={},
     fold=MEAN_FOLD)
_reg("linear", _linear, elementwise=True,
     schema={"t": (float, 0.5)}, fold=LINEAR_FOLD)
_reg("task_arithmetic", _task_arithmetic, elementwise=True,
     schema={"lam": (float, 1.0)}, fold=TASK_ARITH_FOLD)
_reg("negative_merge", _negative_merge, elementwise=True,
     schema={"lam": (float, 0.5)}, fold=NEGATIVE_FOLD)
_reg("fisher_merge", _fisher_merge, elementwise=True,
     schema={"eps": (float, 1e-8)})
_reg("dam", _dam, schema={})
_reg("ada_merging", _ada_merging, schema={"eps": (float, 1e-8)})
_reg("regression_mean", _regression_mean, schema={"eps": (float, 1e-8)})

_reg("ties", _ties, category="sparse", blocked=_ties_blocked,
     schema={"trim": (float, 0.2), "trim_method": (str, "quantile")})
_reg("dare", _dare, needs_key=True, stochastic=True, category="sparse",
     schema={"p": (float, 0.5)})
_reg("dare_ties", _dare_ties, needs_key=True, stochastic=True,
     category="sparse", schema={"p": (float, 0.5)})
_reg("della", _della, needs_key=True, stochastic=True, category="sparse",
     schema={"p_min": (float, 0.2), "p_max": (float, 0.8)})
_reg("model_breadcrumbs", _model_breadcrumbs, category="sparse",
     schema={"beta": (float, 0.1), "gamma": (float, 0.1)})
_reg("emr", _emr, category="sparse", schema={"trim": (float, 0.1)})
_reg("safe_merge", _safe_merge, category="sparse",
     schema={"k_sigma": (float, 6.0)})
_reg("split_unlearn_merge", _split_unlearn_merge, category="sparse",
     schema={})
_reg("star", _star, category="sparse", whole_model=True,
     schema={"keep_frac": (float, 0.75)})

_reg("slerp", _slerp, binary_only=True, category="geometry",
     schema={"t": (float, 0.5)})
_reg("dual_projection", _dual_projection, category="geometry",
     schema={"gamma": (float, 0.5), "eps": (float, 1e-12)})
_reg("svd_knot_tying", _svd_knot_tying, category="geometry",
     whole_model=True, schema={"keep_frac": (float, 0.5)})
_reg("representation_surgery", _representation_surgery,
     category="geometry", schema={"eps": (float, 1e-8)})
_reg("weight_scope_alignment", _weight_scope_alignment,
     category="geometry", schema={})
_reg("led_merge", _led_merge, category="geometry",
     schema={"beta": (float, 5.0), "gamma": (float, 0.7)})
_reg("adarank", _adarank, category="geometry", whole_model=True,
     schema={"keep_frac": (float, 0.5)})

_reg("evolutionary_merge", _evolutionary_merge, needs_key=True,
     stochastic=True, category="search", whole_model=True,
     schema={"pop": (int, 16), "gens": (int, 3), "sigma": (float, 0.3)})
_reg("genetic_merge", _genetic_merge, category="search", whole_model=True,
     schema={"grid": (int, 11), "gens": (int, 3), "reg": (float, 0.05)})
