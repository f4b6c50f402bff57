"""Fused single-pass sampling: Pallas kernel vs its pure-jnp oracle,
the shared-sort XLA fallback vs the sequential per-filter pipeline, tier
agreement, chi-square distribution checks against the PR 2 three-sort
semantics, and the pre-filter logprob-lane contract."""
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from scipy import stats as sp_stats

from repro import sampling as S
from repro.kernels import get_kernel
from repro.kernels.fused_sampling import ref as R
from repro.kernels.fused_sampling.ops import fused_sample
from repro.sampling import SampleFlags, SamplingParams


def _rows(rng, B, V, scale=2.0):
    return jnp.asarray(rng.normal(0.0, scale, (B, V)), jnp.float32)


def _params(rng, B):
    k = jnp.asarray(rng.choice([0, 1, 5, 40, 300], B), jnp.int32)
    p = jnp.asarray(rng.choice([1.0, 0.95, 0.9, 0.5], B), jnp.float32)
    mp = jnp.asarray(rng.choice([0.0, 0.02, 0.1], B), jnp.float32)
    return k, p, mp


# ---------------------------------------------------------------------------
# kernel vs ref.py oracle (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("V,B", [(512, 4), (4096, 3), (1000, 4), (131072, 2)])
def test_kernel_matches_ref(V, B):
    """Interpret-mode kernel == pure-jnp oracle across pow2, odd and
    128k-sized vocabularies (odd V exercises the NEG padding)."""
    rng = np.random.default_rng(V)
    x, g = _rows(rng, B, V), _rows(rng, B, V, 1.0)
    k, p, mp = _params(rng, B)
    out = fused_sample(x, g, k, p, mp, interpret=True)
    # the oracle sees the padded row the kernel binned (catch-all bucket
    # counts include padding; thresholds must still agree exactly)
    pad = (-x.shape[1]) % 512
    xp = jnp.pad(x, ((0, 0), (0, pad)), constant_values=R.NEG)
    gp = jnp.pad(g, ((0, 0), (0, pad)))
    ref = jax.vmap(R.ref_fused_sample)(xp, gp, k, p, mp)
    np.testing.assert_array_equal(out["sampled"], ref["sampled"])
    np.testing.assert_array_equal(out["greedy"], ref["greedy"])
    for key in ("tau", "m", "l"):
        np.testing.assert_allclose(out[key], ref[key], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("V,B,lp_k", [(700, 3, 0), (1536, 4, 4)])
def test_vmem_parked_row_is_bit_identical(V, B, lp_k):
    """``park_vmem=True`` (logits row held in VMEM scratch across the 7
    phases, phase-idle inputs pinned so HBM reads each operand once) is
    bit-identical to the streaming kernel on every output — incl. the
    fused logprob lanes and odd-V NEG padding."""
    rng = np.random.default_rng(V)
    x, g = _rows(rng, B, V), _rows(rng, B, V, 1.0)
    raw = _rows(rng, B, V, 1.0)
    k, p, mp = _params(rng, B)
    kw = dict(lp_k=lp_k, with_lanes=lp_k > 0,
              raw=raw if lp_k > 0 else None)
    parked = fused_sample(x, g, k, p, mp, park_vmem=True, interpret=True,
                          **kw)
    streamed = fused_sample(x, g, k, p, mp, park_vmem=False,
                            interpret=True, **kw)
    assert set(parked) == set(streamed)
    for key in parked:
        np.testing.assert_array_equal(np.asarray(parked[key]),
                                      np.asarray(streamed[key]), err_msg=key)


def test_kernel_matches_xla_fallback_tokens():
    """Same fold_in-derived Gumbel rows through the kernel and the
    shared-sort fallback -> identical sampled tokens (the threshold
    refinement is exact to ~2e-6 nats, far inside the logit spacing)."""
    rng = np.random.default_rng(0)
    B, V = 8, 512
    x = _rows(rng, B, V)
    keys = S.step_keys(S.base_keys(np.arange(B, dtype=np.uint32)),
                       jnp.arange(B, dtype=jnp.int32))
    g = S.token_gumbel(keys, jnp.broadcast_to(
        jnp.arange(V, dtype=jnp.int32)[None], (B, V)))
    k, p, mp = _params(rng, B)
    kern = fused_sample(x, g, k, p, mp, interpret=True)
    from repro.sampling.processors import _NEG_INF, joint_threshold
    tau = joint_threshold(x, k, p, mp, 0)
    masked = jnp.where(x >= tau[:, None], x, _NEG_INF)
    np.testing.assert_array_equal(np.asarray(kern["sampled"]),
                                  np.asarray(jnp.argmax(masked + g, -1)))


def test_kernel_logprob_lanes_match_topk():
    """The kernel's fused raw-logit lanes reproduce the transfer plane's
    log_softmax + lax.top_k math (values and tie-broken indices)."""
    rng = np.random.default_rng(1)
    B, V, K = 3, 700, 4
    x, g = _rows(rng, B, V), _rows(rng, B, V, 1.0)
    raw = _rows(rng, B, V, 1.0)
    out = fused_sample(x, g, jnp.zeros((B,), jnp.int32), jnp.ones((B,)),
                       jnp.zeros((B,)), raw=raw, lp_k=K, with_lanes=True,
                       interpret=True)
    lp = jax.nn.log_softmax(raw, axis=-1)
    v_ref, i_ref = jax.lax.top_k(lp, K)
    logz = out["m_raw"] + jnp.log(out["l_raw"])
    np.testing.assert_array_equal(out["top_idx"], i_ref)
    np.testing.assert_allclose(out["top_vals"] - logz[:, None], v_ref,
                               atol=1e-5)
    chosen = jnp.take_along_axis(
        lp, out["sampled"][:, None].astype(jnp.int32), axis=1)[:, 0]
    recomputed = jnp.take_along_axis(
        raw, out["sampled"][:, None].astype(jnp.int32),
        axis=1)[:, 0] - logz
    np.testing.assert_allclose(recomputed, chosen, atol=1e-5)


def test_kernel_registry():
    op, ref = get_kernel("fused_sampling")
    assert op is fused_sample and ref is R.ref_fused_sample
    assert get_kernel("paged_attention")[0].__name__ == "paged_attention"


# ---------------------------------------------------------------------------
# joint threshold vs the sequential per-filter composition
# ---------------------------------------------------------------------------


def _old_pipeline(x, k, p, mp):
    return S.apply_min_p(S.apply_top_p(S.apply_top_k(
        x, jnp.asarray(k)), jnp.asarray(p)), jnp.asarray(mp))


@pytest.mark.parametrize("k,p,mp", [
    (0, 1.0, 0.0), (5, 1.0, 0.0), (0, 0.7, 0.0), (0, 1.0, 0.05),
    (40, 0.9, 0.02), (3, 0.5, 0.2), (511, 0.99, 0.0), (1, 0.1, 0.5),
])
def test_joint_threshold_equals_sequential_filters(k, p, mp):
    rng = np.random.default_rng(7)
    for trial in range(20):
        x = jnp.asarray(rng.normal(0, 2.0, 512), jnp.float32)
        old_keep = np.asarray(_old_pipeline(x, k, p, mp)) > -1e29
        new = S.joint_filter(x, jnp.asarray(k), jnp.asarray(p),
                             jnp.asarray(mp), 0)
        np.testing.assert_array_equal(np.asarray(new) > -1e29, old_keep)
        # exact passthrough of kept values (identity contract)
        np.testing.assert_array_equal(np.asarray(new)[old_keep],
                                      np.asarray(x)[old_keep])


def test_disabled_defaults_identity_all_tiers():
    """k=0 / p=1 / min_p=0 -> joint filter is a bitwise identity in the
    full-sort, partial-sort and sortless tiers."""
    x = jnp.asarray(np.random.default_rng(3).normal(0, 3.0, 640),
                    jnp.float32)
    for kc in (0, 64, -1):
        out = S.joint_filter(x, jnp.asarray(0), jnp.asarray(1.0),
                             jnp.asarray(0.0), kc)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_topk_tier_matches_full_tier_tokens():
    """The lane tier and the full-sort tier keep the same set AND —
    because the Gumbel noise is token-indexed on every tier — realize
    the identical token stream for the same fold_in keys, so a batch
    composition that flips the tier cannot perturb a sequence's
    stream."""
    rng = np.random.default_rng(11)
    B, V = 16, 256
    x = jnp.asarray(rng.normal(0, 2.0, (B, V)), jnp.float32)
    tau_full = S.joint_threshold(x[0], jnp.asarray(40), jnp.asarray(0.9),
                                 jnp.asarray(0.0), 0)
    tau_lane = S.joint_threshold(x[0], jnp.asarray(40), jnp.asarray(0.9),
                                 jnp.asarray(0.0), 64)
    assert np.array_equal(np.asarray(x[0] >= tau_full),
                          np.asarray(x[0] >= tau_lane))
    row = {"temperature": 0.9, "top_k": 40, "top_p": 0.9, "min_p": 0.0,
           "repetition_penalty": 1.0, "presence_penalty": 0.0,
           "frequency_penalty": 0.0}
    sp = {k: jnp.full((B,), v, jnp.int32 if k == "top_k" else jnp.float32)
          for k, v in row.items()}
    zeros = jnp.zeros((B, V), jnp.int32)
    keys = _keys(B, 31)
    toks = [np.asarray(S.sample(x, zeros, zeros, sp, keys,
                                SampleFlags("xla", False, kc, False,
                                            False)))
            for kc in (0, 64)]
    np.testing.assert_array_equal(toks[0], toks[1])


def test_lane_tier_requires_topk_on_every_drawing_row():
    """Regression: a filterless (temperature-only) row co-batched with a
    top-k row must force the full tier — the lane tier would silently
    truncate its draw to the top-kc logits."""
    f = S.flags_for([SamplingParams(temperature=0.8, top_k=30, seed=0),
                     SamplingParams(temperature=1.1, seed=1)], 4096)
    assert f.kc == 0
    # and the filterless row really does draw outside any small cap:
    rng = np.random.default_rng(2)
    V, n = 64, 2000
    x = jnp.broadcast_to(jnp.asarray(rng.normal(0, 1.0, V), jnp.float32),
                         (n, V))
    row = {"temperature": 1.1, "top_k": 0, "top_p": 1.0, "min_p": 0.0,
           "repetition_penalty": 1.0, "presence_penalty": 0.0,
           "frequency_penalty": 0.0}
    sp = {k: jnp.full((n,), v, jnp.int32 if k == "top_k" else jnp.float32)
          for k, v in row.items()}
    zeros = jnp.zeros((n, V), jnp.int32)
    toks = np.asarray(S.sample(x, zeros, zeros, sp, _keys(n, 3),
                               SampleFlags("xla", False, 0, False, False)))
    assert len(np.unique(toks)) > 32     # mass well outside any kc=32 cap


# ---------------------------------------------------------------------------
# distribution: chi-square vs the PR 2 three-sort pipeline semantics
# ---------------------------------------------------------------------------


def _ref_probs(logits, temperature=1.0, top_k=0, top_p=1.0, min_p=0.0):
    """NumPy ground truth with the sequential three-filter semantics."""
    l = np.asarray(logits, np.float64) / temperature
    if top_k > 0:
        kth = np.sort(l)[::-1][min(top_k, len(l)) - 1]
        l = np.where(l >= kth, l, -np.inf)
    if top_p < 1.0:
        order = np.argsort(l)[::-1]
        pr = np.exp(l[order] - np.max(l))
        pr /= pr.sum()
        cum_excl = np.cumsum(pr) - pr
        l = np.where(l >= l[order][cum_excl < top_p].min(), l, -np.inf)
    if min_p > 0.0:
        pm = np.where(np.isfinite(l),
                      np.exp(l - np.nanmax(np.where(np.isfinite(l), l,
                                                    np.nan))), 0.0)
        l = np.where(pm >= min_p * pm.max(), l, -np.inf)
    pr = np.exp(l - np.max(l[np.isfinite(l)]))
    pr[~np.isfinite(l)] = 0.0
    return pr / pr.sum()


def _chi_square(tokens, probs, alpha=1e-3):
    obs = np.bincount(tokens, minlength=len(probs)).astype(np.float64)
    assert obs[probs == 0].sum() == 0, "drew a filtered (p=0) token"
    exp = len(tokens) * probs
    live = exp > 0
    chi2 = float(((obs[live] - exp[live]) ** 2 / exp[live]).sum())
    crit = float(sp_stats.chi2.ppf(1 - alpha, int(live.sum()) - 1))
    assert chi2 < crit, f"chi2={chi2:.1f} >= crit={crit:.1f}"


def _keys(n, seed):
    return S.step_keys(S.base_keys(np.full((n,), seed, np.uint32)),
                       jnp.arange(n, dtype=jnp.int32))


N_DRAWS = 4000


@pytest.mark.parametrize("kw,kc", [
    ({"temperature": 0.8, "top_k": 10, "top_p": 0.9}, 16),
    ({"temperature": 0.8, "top_k": 10, "top_p": 0.9}, 0),
    ({"temperature": 1.2, "top_p": 0.7}, 0),
    ({"temperature": 0.9, "min_p": 0.05}, -1),
])
def test_chi_square_fallback_matches_old_pipeline(kw, kc):
    """The single-pass fallback (every tier) draws from the identical
    distribution as the PR 2 sequential three-sort pipeline."""
    rng = np.random.default_rng(5)
    V = 24
    logits = rng.normal(0.0, 2.0, V)
    row = {"temperature": 1.0, "top_k": 0, "top_p": 1.0, "min_p": 0.0,
           "repetition_penalty": 1.0, "presence_penalty": 0.0,
           "frequency_penalty": 0.0}
    row.update(kw)
    sp = {k: jnp.full((N_DRAWS,), v,
                      jnp.int32 if k == "top_k" else jnp.float32)
          for k, v in row.items()}
    zeros = jnp.zeros((N_DRAWS, V), jnp.int32)
    toks = S.sample(jnp.broadcast_to(jnp.asarray(logits, jnp.float32),
                                     (N_DRAWS, V)), zeros, zeros, sp,
                    _keys(N_DRAWS, 17),
                    SampleFlags("xla", False, kc, False, False))
    ref = {k: row[k] for k in ("temperature", "top_k", "top_p", "min_p")}
    _chi_square(np.asarray(toks), _ref_probs(logits, **ref))


def test_chi_square_kernel_matches_old_pipeline():
    """Interpret-mode kernel draws (histogram threshold + Gumbel-max over
    the kept set) match the three-sort pipeline's distribution."""
    rng = np.random.default_rng(9)
    n, V = 2000, 24
    logits = rng.normal(0.0, 2.0, V)
    temp = 0.9
    x = jnp.broadcast_to(jnp.asarray(logits / temp, jnp.float32), (n, V))
    g = jax.vmap(lambda kk: jax.random.gumbel(kk, (V,), jnp.float32))(
        _keys(n, 23))
    out = fused_sample(x, g, jnp.full((n,), 6, jnp.int32),
                       jnp.full((n,), 0.85, jnp.float32),
                       jnp.zeros((n,), jnp.float32), interpret=True)
    _chi_square(np.asarray(out["sampled"]),
                _ref_probs(logits, temperature=temp, top_k=6, top_p=0.85))


# ---------------------------------------------------------------------------
# key derivation + flags plumbing
# ---------------------------------------------------------------------------


def test_base_keys_host_matches_prngkey():
    seeds = np.array([0, 1, 77, 2**32 - 1], np.uint32)
    ref = jax.vmap(lambda s: jax.random.PRNGKey(s))(jnp.asarray(seeds))
    np.testing.assert_array_equal(S.base_keys_host(seeds), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(S.base_keys(seeds)),
                                  np.asarray(ref))


def test_flags_for_tiers():
    f = S.flags_for([SamplingParams(temperature=0.8, top_k=40,
                                    top_p=0.95, seed=0)], 4096)
    assert (f.kc, f.pen, f.mixed, f.stops) == (64, False, False, False)
    assert S.flags_for([SamplingParams(temperature=0.8, top_p=0.9,
                                       seed=0)], 4096).kc == 0
    assert S.flags_for([SamplingParams(temperature=0.8, min_p=0.1,
                                       seed=0)], 4096).kc == -1
    # greedy riders never block the lane tier (lane 0 IS the argmax)
    assert S.flags_for([SamplingParams(),
                        SamplingParams(temperature=0.8, top_k=12,
                                       seed=0)], 4096).kc == 16
    mixed = S.flags_for([SamplingParams(),
                         SamplingParams(temperature=1.0, top_k=500,
                                        repetition_penalty=1.2, seed=0,
                                        stop=(3,))], 4096)
    assert (mixed.kc, mixed.pen, mixed.mixed, mixed.stops) == \
        (512, True, True, True)
    # a top-k larger than the vocab degenerates to the full-sort tier
    assert S.flags_for([SamplingParams(temperature=1.0, top_k=4000,
                                       seed=0)], 4096).kc == 0


# (plan, its kc, its tier on a TPU, its tier elsewhere): rollout's
# temperature-1 rows, longtail's Instruct card beside a greedy row, a
# top-p-only row, and lane caps at and above KC_MAX
TIER_PLANS = {
    "rollout": ([SamplingParams(temperature=1.0, seed=0), SamplingParams()],
                -1, "sortless", "sortless"),
    "longtail": ([SamplingParams(temperature=0.7, top_k=20, top_p=0.8,
                                 repetition_penalty=1.1, seed=0),
                  SamplingParams()], 32, "lanes", "lanes"),
    "top_p": ([SamplingParams(temperature=0.8, top_p=0.9, seed=0)],
              0, "kernel", "sort"),
    "kc_max": ([SamplingParams(temperature=1.0, top_k=S.KC_MAX, seed=0)],
               S.KC_MAX, "lanes", "lanes"),
    "above_kc_max": ([SamplingParams(temperature=1.0, top_k=S.KC_MAX + 1,
                                     seed=0)],
                     2 * S.KC_MAX, "kernel", "lanes"),
}


@pytest.mark.parametrize("plan", sorted(TIER_PLANS))
def test_flags_for_runs_the_kernel_only_where_it_wins(plan, monkeypatch):
    """On a TPU the kernel takes the full-sort tier and lane caps above
    KC_MAX; the sortless and smaller lane tiers stay in XLA.  Off the
    TPU every tier is XLA's."""
    sps, kc, on_tpu, off_tpu = TIER_PLANS[plan]
    V = 151936
    f = S.flags_for(sps, V)
    assert (f.kc, f.backend, f.tier) == (kc, "xla", off_tpu)
    monkeypatch.setattr(importlib.import_module("repro.sampling.sample"),
                        "default_backend", lambda: "pallas")
    f = S.flags_for(sps, V)
    assert (f.kc, f.tier) == (kc, on_tpu)
    assert f.backend == ("pallas" if on_tpu == "kernel" else "xla")


@pytest.mark.parametrize("plan", ["rollout", "longtail", "top_p"])
def test_engine_counts_sampled_pages_by_tier(plan):
    """``sample_tier_pages`` counts one page per sampled megastep, under
    its plan's tier; pages with only greedy rows left count nowhere, and
    the scheduler's report sums the counts."""
    from repro.configs import reduced_config
    from repro.core.scheduler import CoroutineScheduler, SchedulerConfig
    from repro.runtime.engine import NodeEngine

    sps, _, _, tier = TIER_PLANS[plan]
    eng = NodeEngine(reduced_config("llama3_2_1b"), max_active=3,
                     max_len=64, page_size=8, seed=0)
    ran = []
    decode_page = eng.decode_page

    def spy(active, P):
        steps = eng.decode_steps
        decode_page(active, P)
        if (eng.decode_steps > steps
                and any(not c.sampling.is_greedy_default for c in active)):
            ran.append(S.flags_for([c.sampling for c in active],
                                   eng.cfg.vocab_size).tier)
    eng.decode_page = spy
    sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8))
    rows = [sps[0], SamplingParams(), sps[0]]
    sched.submit([[5, 6, 7, 8], [9, 10, 11], [12, 13]], [20, 36, 12],
                 sampling=rows)
    report = sched.run(max_ticks=2000)
    assert report["status"] == "completed"
    assert ran and set(ran) == {tier}
    want = {t: ran.count(t) for t in S.SAMPLE_TIERS}
    assert eng.sample_tier_pages == want
    assert report["engine"]["sample_tier_pages"] == want
