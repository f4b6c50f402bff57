"""On-device sampling subsystem: temperature / top-k / top-p decoding.

The subsystem has three layers:

* ``params``     — the host-side :class:`SamplingParams` dataclass carried
  on every :class:`~repro.core.coroutine.SequenceCoroutine`, plus
  ``pack_params`` which turns a list of per-sequence params into the
  (B,)-batched device arrays the jitted pipeline consumes.
* ``processors`` — pure jittable logit processors: penalties and
  temperature, then ONE joint top-k/top-p/min-p value threshold
  (``joint_threshold``) instead of a sort+softmax per filter.  Every
  stage is an exact identity at its parameter's default value, so a
  default-constructed SamplingParams run through the full pipeline
  reproduces greedy argmax bit-for-bit.
* ``sample``     — the batched ``sample`` entry point and the scan-step
  ``sample_step``, dispatching on a static :class:`SampleFlags` plan
  (``flags_for``): one of three XLA tiers (sortless, top-k lanes, full
  sort), or on a TPU for the full sort the Pallas fused-sampling kernel
  (``repro.kernels.fused_sampling``), with penalty/stop/greedy-select
  ops statically dropped when no active slot needs them; plus the
  deterministic PRNG-state helpers threaded as scan carry through the
  fused decode megastep.

Reproducibility contract: the key used for a sequence's t-th sampled
token is ``fold_in(PRNGKey(seed), t)`` — a pure function of the
per-sequence seed and the token index, never of batch composition, slot
index, page size or node placement.  Host-side state (penalty counts,
token index) is re-derivable from the coroutine's token list, so
YIELD/COMBINE/MIGRATE/PARTITION preserve the sampled stream exactly.
"""
from repro.sampling.params import (MAX_STOP_TOKENS, SamplingParams,
                                   derive_fork_seed, pack_params)
from repro.sampling.processors import (apply_min_p, apply_penalties,
                                       apply_temperature, apply_top_k,
                                       apply_top_p, joint_filter,
                                       joint_threshold, process_logits)
from repro.sampling.sample import (DEFAULT_FLAGS, KC_MAX, SAMPLE_TIERS,
                                   SampleFlags, base_keys, base_keys_host,
                                   default_backend, flags_for, init_state,
                                   sample, sample_one, sample_step,
                                   step_keys, stop_hit, token_gumbel)

__all__ = [
    "MAX_STOP_TOKENS", "SamplingParams", "derive_fork_seed", "pack_params",
    "apply_penalties", "apply_temperature", "apply_top_k", "apply_top_p",
    "apply_min_p", "joint_threshold", "joint_filter", "process_logits",
    "DEFAULT_FLAGS", "KC_MAX", "SAMPLE_TIERS", "SampleFlags", "base_keys",
    "base_keys_host", "default_backend", "flags_for", "init_state",
    "sample", "sample_one", "sample_step", "step_keys", "stop_hit",
    "token_gumbel",
]
