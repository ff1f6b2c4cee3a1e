"""Vectorized best-split search over feature histograms.

Reproduces ``FeatureHistogram::FindBestThresholdNumerical`` /
``FindBestThresholdSequence`` (``src/treelearner/feature_histogram.hpp:82-418``)
as one tensor program over all features at once — no per-feature loop:

* two scan directions become two cumulative-sum families over the bin axis;
* the reference's ``continue``/``break`` constraint guards become masks (all
  guarded quantities are monotone along the scan, so masking is equivalent);
* missing-value handling (``MissingType`` none/zero/nan) selects which bins
  contribute to each side and which thresholds are candidates;
* tie-breaking matches the reference scan order: smallest feature index wins,
  then direction -1 (missing defaults left) before +1, then the -1 scan
  prefers the largest threshold and the +1 scan the smallest.

Gain = ``G(left) + G(right) - G(parent) - min_gain_to_split`` with the L1
soft-threshold regularizer ``G(s,h) = max(0, |s|-l1)^2 / (h+l2)``
(``feature_histogram.hpp:255-262``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

K_EPSILON = 1e-15  # reference kEpsilon
MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2


class SplitConfig(NamedTuple):
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    # categorical split search (feature_histogram.hpp:104-223)
    has_categorical: bool = False   # static: skip the cat path entirely if off
    has_missing: bool = True        # static: False skips the dir=+1 scan —
    #                                 without missing values no feature is
    #                                 two_dir (feature_histogram.hpp runs a
    #                                 single direction then too)
    max_cat_threshold: int = 256
    max_cat_group: int = 64
    cat_smooth_ratio: float = 0.01
    min_cat_smooth: float = 5.0
    max_cat_smooth: float = 100.0
    split_find: str = "chain"       # static: fused (per-direction reductions
    #                                 straight off the hot histogram — no
    #                                 packed [F, 2B, 4] candidate arrays) |
    #                                 chain (the historical pack+argmax
    #                                 formulation, the forced A/B baseline).
    #                                 Both produce bit-identical SplitResults.


class SplitResult(NamedTuple):
    """Best split of one leaf (scalar fields) — analogue of SplitInfo
    (src/treelearner/split_info.hpp:17-120)."""
    found: jnp.ndarray        # bool
    gain: jnp.ndarray         # f32, already reduced by gain_shift; -inf if none
    feature: jnp.ndarray      # i32 index into used features; -1 if none
    threshold: jnp.ndarray    # i32 bin threshold (left: bin <= threshold)
    default_left: jnp.ndarray # bool
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray   # f32 count
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray
    is_cat: jnp.ndarray       # bool: categorical split (bitset, not threshold)
    cat_bins: jnp.ndarray     # [B] bool: bins routed LEFT (cat splits only)


def leaf_split_gain(sum_g, sum_h, l1, l2):
    """G(s, h) with L1 soft-thresholding (feature_histogram.hpp:255-262)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return reg * reg / (sum_h + l2)


def leaf_output(sum_g, sum_h, l1, l2):
    """Leaf weight -sign(s)*max(0,|s|-l1)/(h+l2) (feature_histogram.hpp:269-274)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return -jnp.sign(sum_g) * reg / (sum_h + l2)


def _candidate_arrays(hist, parent_g, parent_h, parent_c,
                      num_bin, missing_type, default_bin, feat_valid, cfg):
    """Packed per-candidate arrays [F, 2B] in reference tie-break order:
    per feature, dir=-1 candidates (largest threshold first) then dir=+1
    ascending.  Invalid candidates carry gain = -inf."""
    dtype = hist.dtype
    f, b, _ = hist.shape
    bins = lax.broadcasted_iota(jnp.int32, (f, b), 1)
    nb = num_bin[:, None]
    mt = missing_type[:, None]
    db = default_bin[:, None]
    nan_bin = nb - 1

    l1 = jnp.asarray(cfg.lambda_l1, dtype)
    l2 = jnp.asarray(cfg.lambda_l2, dtype)
    min_data = jnp.asarray(cfg.min_data_in_leaf, dtype)
    min_hess = jnp.asarray(cfg.min_sum_hessian_in_leaf, dtype)

    tot_h = parent_h + 2.0 * K_EPSILON
    gain_shift = leaf_split_gain(parent_g, tot_h, l1, l2)
    min_gain_shift = gain_shift + cfg.min_gain_to_split

    two_dir = (nb > 2) & (mt != MISSING_NONE)
    na_excl = two_dir & (mt == MISSING_NAN)    # dir=-1 keeps NaN bin out of right
    zero_skip = two_dir & (mt == MISSING_ZERO)

    neg_inf = jnp.asarray(-jnp.inf, dtype)

    def eval_candidates(left_g, left_h, left_c, cand):
        right_g = parent_g - left_g
        right_h = tot_h - left_h
        right_c = parent_c - left_c
        ok = (cand
              & (left_c >= min_data) & (right_c >= min_data)
              & (left_h >= min_hess) & (right_h >= min_hess))
        gain = (leaf_split_gain(left_g, left_h, l1, l2)
                + leaf_split_gain(right_g, right_h, l1, l2))
        ok = ok & (gain > min_gain_shift)
        return jnp.where(ok, gain, neg_inf), left_g, left_h, left_c

    # ---- dir = -1 : accumulate from the right; missing defaults LEFT --------
    # channel-stacked: ONE masked [F, B, 3] cumsum/sum per direction
    # instead of three — the find chain runs twice per split inside the
    # grow loop, where op LAUNCH count is the cost that matters on TPU
    keep_m1 = ~((zero_skip & (bins == db)) | (na_excl & (bins == nan_bin)))
    kept = jnp.where(keep_m1[:, :, None], hist, 0.0)
    # right side at threshold t = sum of kept bins strictly above t
    right_m1 = (jnp.sum(kept, axis=1, keepdims=True)
                - jnp.cumsum(kept, axis=1))
    right_g_m1 = right_m1[:, :, 0]
    right_h_m1 = right_m1[:, :, 1] + K_EPSILON
    right_c_m1 = right_m1[:, :, 2]
    left_g_m1 = parent_g - right_g_m1
    left_h_m1 = tot_h - right_h_m1
    left_c_m1 = parent_c - right_c_m1
    cand_m1 = (feat_valid[:, None]
               & (bins <= nb - 2 - na_excl.astype(jnp.int32))
               & ~(zero_skip & (bins == db - 1)))
    gain_m1, lg_m1, lh_m1, lc_m1 = eval_candidates(left_g_m1, left_h_m1,
                                                   left_c_m1, cand_m1)

    # ---- dir = +1 : accumulate from the left; missing defaults RIGHT --------
    # without missing values NO feature is two_dir, so the whole +1 half
    # is statically skipped (candidate width B instead of 2B) — exactly
    # the reference's single-direction scan for missing-free features
    stk_m1 = jnp.stack([gain_m1, lg_m1, lh_m1, lc_m1], axis=-1)
    if not cfg.has_missing:
        packed = jnp.flip(stk_m1, axis=1)
        thr = jnp.flip(bins, axis=1)
        is_m1 = jnp.ones_like(bins, dtype=bool)
        return packed, thr, is_m1, min_gain_shift, tot_h, l1, l2

    keep_p1 = ~(zero_skip & (bins == db))
    kept = jnp.where(keep_p1[:, :, None], hist, 0.0)
    left_p1 = jnp.cumsum(kept, axis=1)
    left_g_p1 = left_p1[:, :, 0]
    left_h_p1 = left_p1[:, :, 1] + K_EPSILON
    left_c_p1 = left_p1[:, :, 2]
    cand_p1 = (feat_valid[:, None] & two_dir
               & (bins <= nb - 2)
               & ~(zero_skip & (bins == db)))
    gain_p1, lg_p1, lh_p1, lc_p1 = eval_candidates(left_g_p1, left_h_p1,
                                                   left_c_p1, cand_p1)

    # ---- combine with reference tie-break order -----------------------------
    # [F, 2B]: dir=-1 flipped (largest threshold first), then dir=+1
    # ascending.  The four per-candidate arrays travel as ONE stacked
    # [F, 2B, 4] tensor (gain, lg, lh, lc): one flip + one concat instead
    # of four of each, and the assembly reads all four with one gather.
    def pack(a_m1, a_p1):
        return jnp.concatenate([jnp.flip(a_m1, axis=1), a_p1], axis=1)

    stk_p1 = jnp.stack([gain_p1, lg_p1, lh_p1, lc_p1], axis=-1)
    packed = jnp.concatenate([jnp.flip(stk_m1, axis=1), stk_p1], axis=1)
    thr = pack(bins, bins)  # pack() flips the dir=-1 half itself
    is_m1 = pack(jnp.ones_like(bins, dtype=bool), jnp.zeros_like(bins, dtype=bool))
    return packed, thr, is_m1, min_gain_shift, tot_h, l1, l2


def _categorical_candidates(hist, parent_g, parent_h, parent_c,
                            num_bin, is_cat, feat_valid, missing_type,
                            cfg: SplitConfig):
    """Categorical split candidates (FindBestThresholdCategorical,
    feature_histogram.hpp:104-223), vectorized over features.

    Bins of each categorical feature are sorted by smoothed grad/hess ratio;
    candidates are prefixes of the sorted order (dir=+1) and of the reversed
    order (dir=-1), up to ``max_cat_threshold`` positions, gated by the
    ``max_cat_group`` accounting which is a short ``lax.scan``.

    Returns (gains [F, 2T], lg, lh, lc, pos [F, 2T], is_p1 [F, 2T],
    order [F, B], used_bin [F]) with candidate order: dir=+1 ascending i,
    then dir=-1 ascending i (the reference's dirs = {1, -1} loop).
    """
    # the sort, the prefix sums and the max_cat_group scan, under a scope of
    # their own inside ``split_find``: a trace reads their device time by it
    with jax.named_scope("cat_scan"):
        dtype = hist.dtype
        f, b, _ = hist.shape
        T = min(int(cfg.max_cat_threshold), b)
        g = hist[:, :, 0]
        h = hist[:, :, 1]
        nb = num_bin                                  # [F]
        # used_bin = num_bin - 1 + (missing == None): the overflow/NaN bin is
        # excluded from the scan unless the mapper saw every category
        used_bin = nb - 1 + (missing_type == MISSING_NONE).astype(jnp.int32)

        l1 = jnp.asarray(cfg.lambda_l1, dtype)
        l2 = jnp.asarray(cfg.lambda_l2, dtype)
        min_data = jnp.asarray(cfg.min_data_in_leaf, dtype)
        min_hess = jnp.asarray(cfg.min_sum_hessian_in_leaf, dtype)

        pg = jnp.broadcast_to(jnp.asarray(parent_g, dtype), (f, 1))[:, 0] \
            if jnp.ndim(parent_g) else jnp.full((f,), parent_g, dtype)
        ph = jnp.broadcast_to(jnp.asarray(parent_h, dtype), (f, 1))[:, 0] \
            if jnp.ndim(parent_h) else jnp.full((f,), parent_h, dtype)
        pc = jnp.broadcast_to(jnp.asarray(parent_c, dtype), (f, 1))[:, 0] \
            if jnp.ndim(parent_c) else jnp.full((f,), parent_c, dtype)
        tot_h = ph + 2.0 * K_EPSILON
        gain_shift = leaf_split_gain(pg, tot_h, l1, l2)
        min_gain_shift = gain_shift + cfg.min_gain_to_split      # [F]

        # smoothing (feature_histogram.hpp:122-126)
        smooth_hess = jnp.minimum(
            cfg.max_cat_smooth,
            jnp.maximum(cfg.cat_smooth_ratio * pc / jnp.maximum(nb, 1),
                        cfg.min_cat_smooth))
        smooth_grad = smooth_hess * pg / jnp.where(ph == 0, 1.0, ph)

        bins_iota = lax.broadcasted_iota(jnp.int32, (f, b), 1)
        in_scan = bins_iota < used_bin[:, None]
        key = (g + smooth_grad[:, None]) / (h + smooth_hess[:, None])
        key = jnp.where(in_scan, key, jnp.inf)        # invalid bins sort last
        order = jnp.argsort(key, axis=1)      # [F, B] bin ids, ascending

        # channel-stacked: ONE sorted gather / cumsum / prefix read over
        # [F, B, 3] instead of three of each (same op-launch rationale as the
        # numerical scan above)
        shist = jnp.take_along_axis(hist, order[:, :, None], axis=1)
        cs = jnp.cumsum(shist, axis=1)                # [F, B, 3]
        last = jnp.clip(used_bin - 1, 0, b - 1)[:, None]
        tot = jnp.take_along_axis(cs, last[:, :, None], axis=1)[:, 0]  # [F, 3]
        tg, th_, tc = tot[:, 0], tot[:, 1], tot[:, 2]

        pos = jnp.arange(T, dtype=jnp.int32)[None, :]            # [1, T]
        # dir=+1: prefix of the sorted order
        take_p1 = jnp.minimum(pos, b - 1)
        pre_p1 = jnp.take_along_axis(cs, take_p1[:, :, None],
                                     axis=1)                # [F, T, 3]
        lg_p1 = pre_p1[:, :, 0]
        lh_p1 = pre_p1[:, :, 1]
        lc_p1 = pre_p1[:, :, 2]
        csc_sorted_c = jnp.take_along_axis(shist[:, :, 2], take_p1, axis=1)
        # dir=-1: prefix of the reversed order = totals minus cumsum at ub-2-i
        idx_m1 = used_bin[:, None] - 2 - pos                     # may be < 0
        clip_m1 = jnp.clip(idx_m1, 0, b - 1)
        pre_m1 = jnp.where((idx_m1 >= 0)[:, :, None],
                           jnp.take_along_axis(cs, clip_m1[:, :, None],
                                               axis=1),
                           0.0)                                  # [F, T, 3]
        lg_m1 = tg[:, None] - pre_m1[:, :, 0]
        lh_m1 = th_[:, None] - pre_m1[:, :, 1]
        lc_m1 = tc[:, None] - pre_m1[:, :, 2]
        step_m1 = jnp.clip(used_bin[:, None] - 1 - pos, 0, b - 1)
        sc_m1 = jnp.take_along_axis(shist[:, :, 2], step_m1, axis=1)

        # dir=-1 skipped when full-categorical and 2*max_cat_threshold covers
        # all bins (feature_histogram.hpp:134-138)
        dir_m1_on = ~((missing_type == MISSING_NONE)
                      & (2 * cfg.max_cat_threshold >= nb))

        cat_ok = feat_valid & is_cat                             # [F]
        base_valid = cat_ok[:, None] & (pos < used_bin[:, None]) # [F, T]

        def stack2(p1, m1):                                  # → [F, 2, T]
            return jnp.stack([p1, m1], axis=1)

        lg2 = stack2(lg_p1, lg_m1)
        lh2 = stack2(lh_p1, lh_m1) + K_EPSILON
        lc2 = stack2(lc_p1, lc_m1)
        step_c = stack2(csc_sorted_c, sc_m1)
        valid2 = stack2(base_valid, base_valid & dir_m1_on[:, None])

        rg2 = pg[:, None, None] - lg2
        rh2 = tot_h[:, None, None] - lh2
        rc2 = pc[:, None, None] - lc2
        cont_ok = (lc2 >= min_data) & (lh2 >= min_hess)
        right_ok = (rc2 >= min_data) & (rh2 >= min_hess)

        # max_cat_group gating: sequential accounting over candidate positions
        # (feature_histogram.hpp:142-147,169-177) — a T-step scan over [F, 2]
        rest0 = jnp.full((f, 2), cfg.max_cat_group, dtype)
        mdpg0 = jnp.maximum(1.0, jnp.floor(pc / cfg.max_cat_group))[:, None] \
            * jnp.ones((1, 2), dtype)
        cnt0 = jnp.zeros((f, 2), dtype)

        def group_step(state, xs):
            cnt, rest, mdpg = state
            step_cnt, cont, rok, rcnt = xs
            cnt = cnt + step_cnt
            accept = cont & rok & (cnt >= mdpg)
            new_rest = jnp.where(accept, rest - 1.0, rest)
            new_mdpg = jnp.where(
                accept & (new_rest > 0),
                jnp.maximum(1.0, jnp.floor(rcnt / jnp.maximum(new_rest, 1.0))),
                mdpg)
            new_cnt = jnp.where(accept, 0.0, cnt)
            return (new_cnt, new_rest, new_mdpg), accept

        xs = (jnp.moveaxis(step_c, 2, 0), jnp.moveaxis(cont_ok, 2, 0),
              jnp.moveaxis(right_ok, 2, 0), jnp.moveaxis(rc2, 2, 0))
        _, accepts = lax.scan(group_step, (cnt0, rest0, mdpg0), xs)
        accept2 = jnp.moveaxis(accepts, 0, 2)                    # [F, 2, T]

        gain2 = (leaf_split_gain(lg2, lh2, l1, l2)
                 + leaf_split_gain(rg2, rh2, l1, l2))
        ok = valid2 & cont_ok & right_ok & accept2 \
            & (gain2 > min_gain_shift[:, None, None])
        gain2 = jnp.where(ok, gain2, -jnp.inf)

        def flat(a):                                   # [F, 2, T] → [F, 2T]
            return a.reshape(f, 2 * T)

        pos2 = jnp.broadcast_to(pos[None, :, :], (f, 2, T))
        is_p1 = jnp.broadcast_to(
            jnp.asarray([True, False])[None, :, None], (f, 2, T))
        return (flat(gain2), flat(lg2), flat(lh2), flat(lc2),
                flat(pos2), flat(is_p1), order, used_bin, min_gain_shift,
                tot_h, l1, l2)


class FusedSplitCtx(NamedTuple):
    """Loop-invariant precomputation of the fused split-find scan.

    Every field depends only on feature metadata + static config — constant
    across a tree's ~L splits — so the grower builds it ONCE per grow call
    (strategy ``setup``) and the while body stops re-deriving the bin iota
    and keep/candidate masks every split the way the chain formulation
    does.  ``keep_p1``/``cand_p1``/``force_right`` are ``None`` when the
    dataset has no missing values (the dir=+1 scan is statically skipped,
    exactly like the chain path)."""
    bins: jnp.ndarray           # [F, B] i32 bin iota
    keep_m1: jnp.ndarray        # [F, B] bool: bins feeding the dir=-1 scan
    cand_m1: jnp.ndarray        # [F, B] bool: dir=-1 candidacy (sans
    #                             feat_valid, which changes per leaf)
    keep_p1: jnp.ndarray        # [F, B] bool | None
    cand_p1: jnp.ndarray        # [F, B] bool | None
    force_right: jnp.ndarray    # [F] bool | None: 2-bin NaN features
    #                             always default right


def make_fused_ctx(num_bin, missing_type, default_bin, num_bins: int,
                   cfg: SplitConfig) -> FusedSplitCtx:
    """Build the loop-invariant fused-scan masks (same boolean algebra as
    ``_candidate_arrays`` — booleans are exact, so hoisting them out of the
    loop body is trivially bit-neutral)."""
    f = num_bin.shape[0]
    b = num_bins
    bins = lax.broadcasted_iota(jnp.int32, (f, b), 1)
    nb = num_bin[:, None]
    mt = missing_type[:, None]
    db = default_bin[:, None]
    nan_bin = nb - 1
    two_dir = (nb > 2) & (mt != MISSING_NONE)
    na_excl = two_dir & (mt == MISSING_NAN)
    zero_skip = two_dir & (mt == MISSING_ZERO)
    keep_m1 = ~((zero_skip & (bins == db)) | (na_excl & (bins == nan_bin)))
    cand_m1 = ((bins <= nb - 2 - na_excl.astype(jnp.int32))
               & ~(zero_skip & (bins == db - 1)))
    if not cfg.has_missing:
        return FusedSplitCtx(bins, keep_m1, cand_m1, None, None, None)
    keep_p1 = ~(zero_skip & (bins == db))
    cand_p1 = two_dir & (bins <= nb - 2) & ~(zero_skip & (bins == db))
    force_right = (num_bin <= 2) & (missing_type == MISSING_NAN)
    return FusedSplitCtx(bins, keep_m1, cand_m1, keep_p1, cand_p1,
                         force_right)


def _fused_numerical(hist, parent_g, parent_h, parent_c,
                     num_bin, missing_type, default_bin, feat_valid,
                     cfg: SplitConfig, feature_base, ctx: FusedSplitCtx):
    """Fused best-split scan: per-direction reductions straight off the
    (still hot) histogram, emitting only the winning ``SplitResult`` —
    the packed ``[F, 2B, 4]`` candidate array, its flip/concat assembly,
    and the candidate-order ``thr``/``is_m1`` tables of the chain path
    never materialize.

    Bit-identity with the chain: every float value entering the selection
    (the masked cumulative sums and ``eval_candidates`` gain algebra) is
    computed by the SAME primitive sequence; only the selection is
    restructured — per-direction row argmax (over the dir=-1 gains
    REVERSED, preserving the largest-threshold-first tie-break) combined
    by the exact packed-order priority (dir=-1 block before dir=+1,
    smallest feature index first), which is equivalent to the chain's
    first-max flat argmax candidate for candidate.

    Returns ``(SplitResult, per_feature_ok [F])``."""
    dtype = hist.dtype
    f, b, _ = hist.shape
    if ctx is None:
        ctx = make_fused_ctx(num_bin, missing_type, default_bin, b, cfg)

    l1 = jnp.asarray(cfg.lambda_l1, dtype)
    l2 = jnp.asarray(cfg.lambda_l2, dtype)
    min_data = jnp.asarray(cfg.min_data_in_leaf, dtype)
    min_hess = jnp.asarray(cfg.min_sum_hessian_in_leaf, dtype)
    tot_h = parent_h + 2.0 * K_EPSILON
    gain_shift = leaf_split_gain(parent_g, tot_h, l1, l2)
    min_gain_shift = gain_shift + cfg.min_gain_to_split
    neg_inf = jnp.asarray(-jnp.inf, dtype)

    def eval_gains(left_g, left_h, left_c, cand):
        # identical arithmetic to the chain's eval_candidates
        right_g = parent_g - left_g
        right_h = tot_h - left_h
        right_c = parent_c - left_c
        ok = (cand
              & (left_c >= min_data) & (right_c >= min_data)
              & (left_h >= min_hess) & (right_h >= min_hess))
        gain = (leaf_split_gain(left_g, left_h, l1, l2)
                + leaf_split_gain(right_g, right_h, l1, l2))
        ok = ok & (gain > min_gain_shift)
        return jnp.where(ok, gain, neg_inf)

    # ---- dir = -1 : accumulate from the right; missing defaults LEFT ----
    # without missing values no bin is ever excluded (two_dir is all-False
    # so keep_m1 is all-True) — the masking select is the identity and is
    # statically skipped (where(True, hist, 0) == hist bit for bit)
    kept = (jnp.where(ctx.keep_m1[:, :, None], hist, 0.0)
            if cfg.has_missing else hist)
    right_m1 = (jnp.sum(kept, axis=1, keepdims=True)
                - jnp.cumsum(kept, axis=1))
    lg_m1 = parent_g - right_m1[:, :, 0]
    lh_m1 = tot_h - (right_m1[:, :, 1] + K_EPSILON)
    lc_m1 = parent_c - right_m1[:, :, 2]
    gains_m1 = eval_gains(lg_m1, lh_m1, lc_m1,
                          feat_valid[:, None] & ctx.cand_m1)
    # chain order puts dir=-1 candidates largest-threshold-first: the row
    # argmax over the REVERSED gains is exactly that order's first max
    flipped_m1 = gains_m1[:, ::-1]
    jm = jnp.argmax(flipped_m1, axis=1)
    gm = jnp.max(flipped_m1, axis=1)

    if cfg.has_missing:
        # ---- dir = +1 : accumulate from the left; missing defaults RIGHT
        kept = jnp.where(ctx.keep_p1[:, :, None], hist, 0.0)
        left_p1 = jnp.cumsum(kept, axis=1)
        lg_p1 = left_p1[:, :, 0]
        lh_p1 = left_p1[:, :, 1] + K_EPSILON
        lc_p1 = left_p1[:, :, 2]
        gains_p1 = eval_gains(lg_p1, lh_p1, lc_p1,
                              feat_valid[:, None] & ctx.cand_p1)
        jp = jnp.argmax(gains_p1, axis=1)
        gp = jnp.max(gains_p1, axis=1)
        best_f = jnp.maximum(gm, gp)     # per-feature winner, dir=-1 first
    else:
        best_f = gm

    # smallest feature index wins ties — argmax's first-max, like the
    # chain's feature-major flat argmax
    fi = jnp.argmax(best_f).astype(jnp.int32)
    best_gain = best_f[fi]
    found = best_gain > neg_inf

    bin_m1 = (b - 1 - jm[fi]).astype(jnp.int32)
    if cfg.has_missing:
        use_m1 = gm[fi] >= gp[fi]        # ties: dir=-1 precedes dir=+1
        pos_p1 = jp[fi].astype(jnp.int32)
        threshold = jnp.where(use_m1, bin_m1, pos_p1)
        left_sum_g = jnp.where(use_m1, lg_m1[fi, bin_m1], lg_p1[fi, pos_p1])
        left_sum_h_raw = jnp.where(use_m1, lh_m1[fi, bin_m1],
                                   lh_p1[fi, pos_p1])
        left_count = jnp.where(use_m1, lc_m1[fi, bin_m1], lc_p1[fi, pos_p1])
        default_left = jnp.where(found, use_m1, True)
        # 2-bin NaN features always default right (chain _result_from_index)
        default_left = jnp.where(found & ctx.force_right[fi], False,
                                 default_left)
    else:
        threshold = bin_m1
        left_sum_g = lg_m1[fi, bin_m1]
        left_sum_h_raw = lh_m1[fi, bin_m1]
        left_count = lc_m1[fi, bin_m1]
        default_left = jnp.ones((), bool)   # chain: is_m1 always True here

    right_sum_g = parent_g - left_sum_g
    right_sum_h_raw = tot_h - left_sum_h_raw
    right_count = parent_c - left_count

    res = SplitResult(
        found=found,
        gain=jnp.where(found, best_gain - min_gain_shift, neg_inf),
        feature=jnp.where(found, fi + feature_base, -1),
        threshold=jnp.where(found, threshold, 0).astype(jnp.int32),
        default_left=default_left,
        left_sum_g=left_sum_g,
        left_sum_h=left_sum_h_raw - K_EPSILON,
        left_count=left_count,
        right_sum_g=right_sum_g,
        right_sum_h=right_sum_h_raw - K_EPSILON,
        right_count=right_count,
        left_output=leaf_output(left_sum_g, left_sum_h_raw, l1, l2),
        right_output=leaf_output(right_sum_g, right_sum_h_raw, l1, l2),
        is_cat=jnp.zeros((), bool),
        cat_bins=jnp.zeros((b,), bool),
    )
    return res, best_f > neg_inf


def _result_from_index(idx, packed, thr, is_m1,
                       parent_g, parent_c, num_bin, missing_type,
                       min_gain_shift, tot_h, l1, l2, nf, b, feature_base=0):
    """Assemble a SplitResult from a flat candidate index into [F, 2B]
    (``packed`` stacks (gain, lg, lh, lc) on the last axis)."""
    neg_inf = jnp.asarray(-jnp.inf, packed.dtype)
    row = packed.reshape(-1, 4)[idx]          # one gather: all four values
    best_gain = row[0]
    found = best_gain > neg_inf
    # candidate width is B (single-direction, no missing) or 2B
    feature_local = (idx // packed.shape[1]).astype(jnp.int32)
    feature = jnp.where(found, feature_local + feature_base, -1)
    threshold = jnp.where(found, thr.reshape(-1)[idx], 0)
    default_left = jnp.where(found, is_m1.reshape(-1)[idx], True)
    # 2-bin NaN features always default right (feature_histogram.hpp:97-100)
    fi = jnp.clip(feature_local, 0, nf - 1)
    force_right = (num_bin[fi] <= 2) & (missing_type[fi] == MISSING_NAN)
    default_left = jnp.where(found & force_right, False, default_left)

    left_sum_g = row[1]
    left_sum_h_raw = row[2]
    left_count = row[3]
    right_sum_g = parent_g - left_sum_g
    right_sum_h_raw = tot_h - left_sum_h_raw
    right_count = parent_c - left_count

    return SplitResult(
        found=found,
        gain=jnp.where(found, best_gain - min_gain_shift, neg_inf),
        feature=feature,
        threshold=threshold.astype(jnp.int32),
        default_left=default_left,
        left_sum_g=left_sum_g,
        left_sum_h=left_sum_h_raw - K_EPSILON,
        left_count=left_count,
        right_sum_g=right_sum_g,
        right_sum_h=right_sum_h_raw - K_EPSILON,
        right_count=right_count,
        left_output=leaf_output(left_sum_g, left_sum_h_raw, l1, l2),
        right_output=leaf_output(right_sum_g, right_sum_h_raw, l1, l2),
        is_cat=jnp.zeros((), bool),
        cat_bins=jnp.zeros((b,), bool),
    )


def _cat_result_from_index(idx, gains_flat, lg, lh, lc, pos, is_p1,
                           order, used_bin, parent_g, parent_c,
                           min_gain_shift, tot_h, l1, l2, nf, b, t2,
                           feature_base=0) -> SplitResult:
    """Assemble a categorical SplitResult from a flat index into [F, 2T]."""
    neg_inf = jnp.asarray(-jnp.inf, gains_flat.dtype)
    best_gain = gains_flat[idx]
    found = best_gain > neg_inf
    feature_local = (idx // t2).astype(jnp.int32)
    fi = jnp.clip(feature_local, 0, nf - 1)
    p = pos.reshape(-1)[idx]
    p1 = is_p1.reshape(-1)[idx]
    ub = used_bin[fi]

    # bins routed left = sorted positions [0..p] (dir=+1) or
    # [ub-1-p..ub-1] (dir=-1); rank = inverse permutation of the sort
    order_row = lax.dynamic_index_in_dim(order, fi, axis=0, keepdims=False)
    rank = jnp.argsort(order_row)                 # rank[bin] = sorted position
    member = jnp.where(p1, rank <= p, rank >= ub - 1 - p) & (rank < ub)
    cat_bins = found & member

    shift = min_gain_shift[fi] if jnp.ndim(min_gain_shift) else min_gain_shift
    toth = tot_h[fi] if jnp.ndim(tot_h) else tot_h
    pg = parent_g[fi] if jnp.ndim(parent_g) else parent_g
    pc = parent_c[fi] if jnp.ndim(parent_c) else parent_c

    left_sum_g = lg.reshape(-1)[idx]
    left_sum_h_raw = lh.reshape(-1)[idx]
    left_count = lc.reshape(-1)[idx]
    right_sum_g = pg - left_sum_g
    right_sum_h_raw = toth - left_sum_h_raw
    right_count = pc - left_count

    return SplitResult(
        found=found,
        gain=jnp.where(found, best_gain - shift, neg_inf),
        feature=jnp.where(found, fi + feature_base, -1),
        threshold=jnp.zeros((), jnp.int32),
        default_left=jnp.zeros((), bool),        # cat splits default right
        left_sum_g=left_sum_g,
        left_sum_h=left_sum_h_raw - K_EPSILON,
        left_count=left_count,
        right_sum_g=right_sum_g,
        right_sum_h=right_sum_h_raw - K_EPSILON,
        right_count=right_count,
        left_output=leaf_output(left_sum_g, left_sum_h_raw, l1, l2),
        right_output=leaf_output(right_sum_g, right_sum_h_raw, l1, l2),
        is_cat=found,
        cat_bins=cat_bins,
    )


def best_split(hist: jnp.ndarray,
               parent_g: jnp.ndarray, parent_h: jnp.ndarray, parent_c: jnp.ndarray,
               num_bin: jnp.ndarray, missing_type: jnp.ndarray,
               default_bin: jnp.ndarray, feat_valid: jnp.ndarray,
               cfg: SplitConfig, feature_base: int = 0,
               is_cat: jnp.ndarray = None, with_feat_ok: bool = False,
               fused_ctx: FusedSplitCtx = None):
    """Best split (numerical or categorical) across all features of one leaf.

    hist: [F, B, 3] (sum_g, sum_h, count); num_bin/missing_type/default_bin:
    [F] i32; feat_valid: [F] bool (feature_fraction & non-trivial); is_cat:
    [F] bool (None ⇒ all numerical).  parent_*: scalars for the leaf.
    ``feature_base`` offsets the reported feature index (feature-parallel
    shards).

    ``with_feat_ok=True`` additionally returns the per-feature
    ``is_splittable`` flags [F] — True when the feature produced ANY
    candidate beating min_gain_shift on this leaf.  The reference prunes
    features whose parent leaf had no such candidate from the entire
    subtree (serial_tree_learner.cpp:406-417), so the grower records
    these flags per leaf and gates children's scans with them.

    ``cfg.split_find`` selects the numerical-scan formulation: ``fused``
    (per-direction reductions, no packed candidate arrays; optionally fed
    the loop-invariant ``fused_ctx`` the grower hoists) or ``chain`` (the
    historical pack+argmax form).  Both are bit-identical — pinned in
    tests/test_split_find.py; the categorical scan is shared.
    """
    f, b, _ = hist.shape
    use_cat = cfg.has_categorical and is_cat is not None
    num_valid = feat_valid & ~is_cat if use_cat else feat_valid
    if cfg.split_find == "fused":
        num_res, num_ok = _fused_numerical(
            hist, parent_g, parent_h, parent_c, num_bin, missing_type,
            default_bin, num_valid, cfg, feature_base, fused_ctx)
        if not use_cat:
            if with_feat_ok:
                return num_res, num_ok
            return num_res
        return _combine_categorical(
            hist, num_res, num_ok, parent_g, parent_h, parent_c, num_bin,
            missing_type, is_cat, feat_valid, cfg, feature_base, f, b,
            with_feat_ok)
    (packed, thr, is_m1,
     min_gain_shift, tot_h, l1, l2) = _candidate_arrays(
        hist, parent_g, parent_h, parent_c, num_bin, missing_type,
        default_bin, num_valid, cfg)
    gains = packed[:, :, 0]
    idx = jnp.argmax(gains.reshape(-1))
    num_res = _result_from_index(idx, packed, thr, is_m1,
                                 parent_g, parent_c, num_bin, missing_type,
                                 min_gain_shift, tot_h, l1, l2, f, b,
                                 feature_base)
    if not use_cat:
        if with_feat_ok:
            return num_res, jnp.max(gains, axis=1) > -jnp.inf
        return num_res
    return _combine_categorical(
        hist, num_res, jnp.max(gains, axis=1) > -jnp.inf, parent_g,
        parent_h, parent_c, num_bin, missing_type, is_cat, feat_valid, cfg,
        feature_base, f, b, with_feat_ok)


def _combine_categorical(hist, num_res, num_ok, parent_g, parent_h, parent_c,
                         num_bin, missing_type, is_cat, feat_valid,
                         cfg: SplitConfig, feature_base, f, b, with_feat_ok):
    """Categorical scan + numerical-vs-categorical combine, shared by the
    chain and fused numerical paths (the categorical candidate machinery is
    identical either way)."""
    dtype = hist.dtype
    l1 = jnp.asarray(cfg.lambda_l1, dtype)
    l2 = jnp.asarray(cfg.lambda_l2, dtype)
    (cgains, clg, clh, clc, cpos, cp1, order, used_bin,
     c_shift, c_tot_h, _, _) = _categorical_candidates(
        hist, parent_g, parent_h, parent_c, num_bin, is_cat, feat_valid,
        missing_type, cfg)
    cflat = cgains.reshape(-1)
    cidx = jnp.argmax(cflat)
    cat_res = _cat_result_from_index(cidx, cflat, clg, clh, clc, cpos, cp1,
                                     order, used_bin, parent_g, parent_c,
                                     c_shift, c_tot_h, l1, l2, f, b,
                                     cgains.shape[1], feature_base)
    # features are either numerical or categorical; reproduce the serial
    # learner's feature-major tie-break (smallest feature index wins)
    pick_cat = cat_res.found & (~num_res.found
                                | (cat_res.gain > num_res.gain)
                                | ((cat_res.gain == num_res.gain)
                                   & (cat_res.feature < num_res.feature)))
    res = jax.tree.map(lambda a, c: jnp.where(pick_cat, c, a),
                       num_res, cat_res)
    if with_feat_ok:
        ok = jnp.where(is_cat, jnp.max(cgains, axis=1) > -jnp.inf, num_ok)
        return res, ok
    return res


def per_feature_best_gain(hist: jnp.ndarray,
                          parent_g, parent_h, parent_c,
                          num_bin, missing_type, default_bin, feat_valid,
                          cfg: SplitConfig, is_cat: jnp.ndarray = None) -> jnp.ndarray:
    """Best gain per feature [F] (gain - gain_shift; -inf if unsplittable).

    Used by the voting-parallel learner to pick each worker's top-k vote
    features (voting_parallel_tree_learner.cpp:255-330)."""
    use_cat = cfg.has_categorical and is_cat is not None
    num_valid = feat_valid & ~is_cat if use_cat else feat_valid
    (packed, _, _, min_gain_shift, _, _, _) = _candidate_arrays(
        hist, parent_g, parent_h, parent_c, num_bin, missing_type,
        default_bin, num_valid, cfg)
    best = jnp.max(packed[:, :, 0], axis=1)
    # parent sums may be per-feature [F, 1] (voting learner's local stats)
    shift = jnp.asarray(min_gain_shift)
    if shift.ndim:
        shift = shift.reshape(-1)
    out = jnp.where(best > -jnp.inf, best - shift, -jnp.inf)
    if use_cat:
        (cgains, _, _, _, _, _, _, _, c_shift, _, _, _) = \
            _categorical_candidates(hist, parent_g, parent_h, parent_c,
                                    num_bin, is_cat, feat_valid,
                                    missing_type, cfg)
        cbest = jnp.max(cgains, axis=1)
        cout = jnp.where(cbest > -jnp.inf, cbest - c_shift, -jnp.inf)
        out = jnp.maximum(out, cout)
    return out
