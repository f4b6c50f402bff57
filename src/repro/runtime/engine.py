"""Per-node batch-inference engine: real JAX execution + coroutine slots.

One NodeEngine = one node's GPU pool.  It owns a dense device decode cache
with `max_active` sequence slots, a paged host store (single source of
truth, §5.2), a page allocator (two-page lazy allocation), and jitted
prefill/decode steps.  The CoroutineScheduler drives it exclusively
through the formal ExecutionBackend slot protocol (core/backend.py —
conformance declared below), so the exact same scheduling code also
drives the cluster simulator.

Logprobs: when any active coroutine requests them, the megastep emits the
packed ``(P, B, 2+2K)`` plane of ``models.transformer.pack_logprob_block``
(chosen-token logprob + top-K alternatives from the RAW model logits)
instead of the bare token block — the page still crosses in ONE
device→host transfer; ``_apply_block`` unpacks it host-side.

Fused decode megastep (default)
-------------------------------
``decode_page`` runs one jitted ``lax.scan`` over the whole page: tokens,
lengths, the per-slot ``remaining`` countdown and the KV cache stay on
device (cache donated across the scan), sampled tokens self-feed, and
finished slots are masked out.  The page returns as ONE ``(P, max_active)``
token block — exactly one device→host transfer per page (counted in
``d2h_transfers``; the per-token loop pays one per token plus Python
bookkeeping, the dispatch-bound regime benchmarks/decode_throughput.py
measures).  Ragged pages decompose into chained pow2-sized scan chunks
(40 -> 32+8) so the engine holds at most ``log2(P)`` megastep
executables while never running a wasted masked step.

Host-sync contract: after ``decode_page``, coroutine state (generated/
last_token/length) is already updated from the block; the page's KV then
moves to the host store through a two-stage software pipeline —
``stage_appends`` issues ONE jitted batched gather of every dirty slot's
new window and starts the device→host copy asynchronously
(``copy_to_host_async``), snapshotting each slot's ``[synced, length)``
span at issue time; ``drain_appends`` later materializes the blob into
per-page host-store appends.  The scheduler drains page N's blob at page
N+1's SYNC_DRAIN phase, so the PCIe transfer rides behind the next
megastep instead of blocking the loop (``overlap=False`` restores the
blocking gather-transfer-append of the seed path; ``sync_appends`` is
stage+drain in one call).  In-flight staged bytes are metered through a
real ``memory.buffers.RingBuffer``: a stage that would overflow
``ring_buffer_bytes`` falls back to a synchronous drain first (counted
in ``sync_stalls`` — the signal the §5.4 plan sizes the buffer against).

Slot installs (COMBINE/refill) are likewise staged host-side and applied
in ONE jitted multi-slot scatter — cache leaves, last tokens and lengths
together — right before the next consumer of device state (decode or
extract), so refilling n slots costs one dispatch instead of
``n * (leaves + 2)`` eager scatters.

Supports dense and MoE families (caches {"k","v"}); set
``module_granularity=True`` to decode through the Algorithm-1 module
runtime (per-sub-batch attention + COMBINE before MoE), which fuses the
same way via ``ModuleRuntime.forward_decode_page``.

Robustness (§5.6): the engine emits a ``heartbeat()`` each scheduler
round and routes its risky host transfers — the staged d2h copy issue
("stage"), blob materialization ("drain"), the batched install scatter
("install") and migrate blob moves ("migrate") — through ``transfer``,
the bounded-exponential-backoff retry envelope of ``runtime/faults.py``.
A transfer that exhausts its retry budget dead-letters: the lost blob's
host-store entries are dropped (``_abandon_blob`` — a lagging checkpoint
must never feed a migrate) and the scheduler escalates the node to
NODE_FAILURE.  An injected ``NodeFaults`` view (``faults=``) makes the
engine honor a deterministic FaultPlan: death/oom refuse admissions and
compute, stale windows suppress heartbeats, transfer faults exercise the
retry path — all keyed to scheduler rounds, hence replayable.

Sampling: when any active coroutine carries non-default SamplingParams,
``decode_page`` switches to the sampled megastep variant — same fused
scan with the per-slot PRNG position and penalty counts riding the carry
(repro.sampling) — still one device→host transfer per page.  The engine
derives a static ``SampleFlags`` plan from the active batch (Pallas
kernel vs shared-sort XLA tier, penalty/stop/greedy-select skips) that
is part of the megastep jit key.  Per-slot sampling state is re-derived
from the coroutine at ``install_slot`` (keys are fold_in(seed,
token_index), counts a bincount of its tokens) — staged host-side and
flushed to the device in ONE batched scatter at the next sampled page,
so slot churn never perturbs a sequence's sampled stream and never pays
per-slot eager dispatches.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from functools import partial
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import sampling as smp
from repro.core.backend import validate_backend
from repro.core.coroutine import Phase, SequenceCoroutine, Status
from repro.core.forward import ModuleRuntime, _lru_get
from repro.core.primitives import PrimitiveStats
from repro.memory.allocator import PageAllocator
from repro.memory.buffers import RingBuffer
from repro.memory.paged_kv import HostKVStore
from repro.models import transformer as T
from repro.models.api import MeshAxes, ModelConfig
from repro.runtime.failure import DeviceStatus, Heartbeat
from repro.runtime.faults import (NodeFaults, RetryPolicy,
                                  TransferDeadLetter, guarded_transfer)

_PREFILL_JIT_CAP = 8    # LRU cap on (B, S)-bucketed prefill executables
_GATHER_JIT_CAP = 16    # LRU cap on (n, W)-bucketed sync-gather executables
_INSTALL_JIT_CAP = 8    # LRU cap on n-bucketed multi-slot install scatters
# staging-path PCIe-class bandwidth for the ring buffer's timing model
# (core/plan.py Hardware.host_link_bw); the live gate only uses occupancy
_HOST_LINK_BW = 32e9
# the per-slot sampling-params rows the batched sampler consumes
_SAMPLE_ROW_KEYS = ("temperature", "top_k", "top_p", "min_p",
                    "repetition_penalty", "presence_penalty",
                    "frequency_penalty")
# LRU cap on (scan-length, sampled, lp_k)-keyed megasteps — sized for all
# pow2 chunk sizes of a page x {greedy, sampled} x {no-lp, lp} variants
# coexisting without steady-state eviction/re-jit churn
_MEGASTEP_JIT_CAP = 32


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class _InFlightSync:
    """One staged KV blob: the device array whose async device→host copy
    has been issued, plus everything needed to land it in the host store
    later — the leaf layout and the per-slot ``(seq_id, start, n, first)``
    spans snapshotted at issue time (slot reuse after the snapshot cannot
    corrupt it: the gather already copied the values)."""
    __slots__ = ("blob", "metas", "snaps", "nbytes", "name")

    def __init__(self, blob, metas, snaps, nbytes, name):
        self.blob = blob
        self.metas = metas
        self.snaps = snaps
        self.nbytes = nbytes
        self.name = name


def _np_top_k_idx(x: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k largest entries, ties broken by LOWEST index —
    matching ``jax.lax.top_k`` so host-side (prefill / looped-baseline)
    top-logprobs agree with the device plane.  (``argsort()[::-1]`` would
    break ties by highest index.)"""
    return np.argsort(-x, kind="stable")[:k]


def _np_log_softmax(x: np.ndarray) -> np.ndarray:
    """Host-side log-softmax over the last axis (prefill / looped-baseline
    logprobs; the fused path computes this on device)."""
    x = np.asarray(x, np.float32)
    m = x.max(axis=-1, keepdims=True)
    e = np.exp(x - m)
    return (x - m) - np.log(e.sum(axis=-1, keepdims=True))


def _prefill_logits(cfg: ModelConfig, axes: MeshAxes, params, tokens, last):
    """Prompt forward over a (B, S) bucket: the logits at each row's last
    prompt position ``last`` and the full-prompt caches."""
    h, _, caches = T._backbone(cfg, axes, params, {"tokens": tokens}, None,
                               True, False)               # h final-normed
    hl = jnp.take_along_axis(h, last[:, None, None].astype(
        jnp.int32).repeat(h.shape[-1], -1), axis=1)
    return T.logits_fn(cfg, params, hl), caches


def _install_scatter(cache, tokens, lengths, idx, upd, tok, ln):
    """Write n staged slots (cache leaves, last token, length) at once."""
    new = dict(cache)
    for nm, u in upd.items():
        new[nm] = cache[nm].at[:, idx].set(u)
    return new, tokens.at[idx].set(tok), lengths.at[idx].set(ln)


def _gather_blob(names, cache, slots, pos):
    """Every dirty slot's window of every leaf, (n, 1) slots x (n, W)
    positions, as ONE (L, n, W, F_total) blob."""
    parts = []
    for nm in names:
        seg = cache[nm][:, slots, pos]                  # (L, n, W, *trail)
        parts.append(seg.reshape(seg.shape[:3] + (-1,)))
    return jnp.concatenate(parts, axis=-1)


class NodeEngine:
    def __init__(self, cfg: ModelConfig, *, node_id: int = 0,
                 max_active: int = 8, max_len: int = 256,
                 page_size: int = 32, num_devices: int = 8,
                 device_pages: Optional[int] = None,
                 module_granularity: bool = False, b_attn: int = 0,
                 fused: bool = True, overlap: bool = True,
                 ring_buffer_bytes: Optional[int] = None,
                 restore_ring_bytes: Optional[int] = None, seed: int = 0,
                 faults: Optional[NodeFaults] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 enable_prefix: bool = True, device=None):
        assert cfg.family in ("dense", "moe") and cfg.sliding_window == 0, \
            "mini-engine supports dense/moe caches; see cluster sim for rest"
        self.cfg = cfg
        self.axes = MeshAxes(batch=("data",), model="model")
        self.node_id = node_id
        self.max_active = max_active
        self.max_len = max_len
        self.num_devices = num_devices
        self.page_size = page_size
        self.fused = fused
        self.overlap = overlap
        # the chip this engine runs on (default: the first device).  Every
        # array it owns is committed there, so jitted steps run there and
        # several engines in one process each keep to their own chip.
        self.device = device if device is not None else jax.devices()[0]

        with jax.default_device(self.device):
            self.params = self._put(T.init_params(cfg,
                                                  jax.random.PRNGKey(seed)))
            self.cache = self._put(T.init_cache(cfg, max_active, max_len))
        self.host_store = HostKVStore(page_size, enable_prefix=enable_prefix)
        total_pages = device_pages or (max_active * max_len // page_size * 2)
        self.allocator = PageAllocator(total_pages, page_size)
        self.stats = PrimitiveStats()

        # ---- robustness (§5.6): fault injection + guarded transfers -------
        self.faults = faults                    # NodeFaults view or None
        self.retry_policy = retry_policy or RetryPolicy()
        self.transfer_stats = {"retries": 0, "timeouts": 0, "dead_letters": 0}
        self.dead_lettered = False      # scheduler escalates to NODE_FAILURE
        self.oom_rejections = 0         # admissions refused by an oom fault
        self.straggler_steps = 0        # decode steps run under a straggler
        self.abandoned_blobs = 0        # staged blobs lost to dead-letters

        # device slot arrays (the cache was built with the params above)
        self.tokens = self._put(np.zeros((max_active,), np.int32))
        self.lengths = self._put(np.zeros((max_active,), np.int32))
        self.slot_owner: List[Optional[int]] = [None] * max_active
        self.synced_len: Dict[int, int] = {}

        # per-slot sampling params (host mirror, uploaded lazily) + the
        # device-resident sampling state that rides the megastep carry
        V = T.padded_vocab(cfg)
        self._sp_host = smp.pack_params([smp.SamplingParams()] * max_active,
                                        list(range(max_active)))
        self._sp_dev: Optional[Dict] = None
        self._sample_state = self._put({
            "base_key": np.zeros((max_active, 2), np.uint32),
            "gen_count": np.zeros((max_active,), np.int32),
            "counts": np.zeros((max_active, V), np.int32),
            "prompt_counts": np.zeros((max_active, V), np.int32),
        })
        # slot installs stage their re-derived sampling state here (host
        # numpy, keyed by slot so a re-install overwrites) and the next
        # sampled decode_page scatters everything in one batch — per-slot
        # eager device dispatches were the dominant sampled-path overhead
        self._pending_smp: "OrderedDict[int, tuple]" = OrderedDict()
        self._prefill_sample_cache: "OrderedDict[tuple, object]" = \
            OrderedDict()
        self._flush_cache: "OrderedDict[int, object]" = OrderedDict()

        self._decode = jax.jit(
            lambda p, c, t, l: T.decode_step(cfg, self.axes, p, c, t, l),
            donate_argnums=(1,))
        self._decode_logits = None      # lazy: looped-baseline logprob path
        self._megastep_cache: "OrderedDict[tuple, object]" = OrderedDict()
        self._prefill_cache: "OrderedDict[tuple, object]" = OrderedDict()
        # executables built by the jit LRUs, and the host wall time of
        # their first call (trace, lower, compile or cache load)
        self.jit_builds = 0
        self.jit_build_s = 0.0
        self.module_rt = (ModuleRuntime(cfg, self.axes, self.params,
                                        owner=self)
                          if module_granularity else None)
        self.b_attn = b_attn or max_active
        self.decode_steps = 0
        self.tokens_out = 0.0       # cumulative effective tokens emitted —
        #                             heartbeat progress counter; an injected
        #                             straggler divides the increment by its
        #                             factor (the fault is honored where it
        #                             is observed: a real node cannot be
        #                             slowed deterministically, so its beat
        #                             under-reports progress instead)
        self.prefill_tokens = 0
        self.prefill_tokens_saved = 0   # prompt tokens served from shared KV
        self.d2h_transfers = 0      # device→host copies through _to_host

        # ---- pipelined host-KV staging (stage_appends / drain_appends) ----
        # stable leaf layout of the concatenated sync blob
        self._blob_metas = [(name, leaf.shape[3:],
                             int(np.prod(leaf.shape[3:])) if leaf.shape[3:]
                             else 1)
                            for name, leaf in self.cache.items()]
        self._inflight: Deque[_InFlightSync] = deque()
        self._gather_cache: "OrderedDict[tuple, object]" = OrderedDict()
        # the live backpressure gate: worst case one page blob is every
        # slot dirty for a full page; default capacity = two of those
        # (pipeline depth 1 + the blob being staged) so steady state
        # never stalls while a runaway pipeline cannot hoard host RAM
        page_blob = sum(leaf.dtype.itemsize * leaf.shape[0]
                        * _pow2(max_active) * _pow2(page_size) * f
                        for (_, _, f), leaf in zip(self._blob_metas,
                                                   self.cache.values()))
        self.ring = RingBuffer(ring_buffer_bytes or 2 * page_blob,
                               _HOST_LINK_BW)
        self._sync_tag = 0
        self.sync_stages = 0        # async-staged blobs
        self.sync_stalls = 0        # ring-full fallbacks to synchronous drain
        self.sync_wait_s = 0.0      # wall time blocked materializing blobs

        # ---- batched slot installs (COMBINE/refill) -----------------------
        # slot -> (cache slices, last_token, length), flushed in one jitted
        # multi-slot scatter before the next consumer of device state
        self._pending_install: "OrderedDict[int, tuple]" = OrderedDict()
        self._install_cache: "OrderedDict[int, object]" = OrderedDict()
        self.install_s = 0.0            # host wall time flushing installs
        self.slots_installed = 0        # slots written (not pow2 padding)
        # sampled decode pages by the path their plan samples on
        self.sample_tier_pages = dict.fromkeys(smp.SAMPLE_TIERS, 0)

        # ---- staged h2d restores (stage_restore / take_restore) -----------
        # the host→device mirror of the d2h sync pipeline: a suspended
        # sequence's checkpoint is device_put BEFORE its COMBINE, metered
        # through its own h2d ring buffer (same stage/drain discipline as
        # the d2h ring; a separate instance because one full-sequence
        # restore dwarfs a decode-page blob, and restore prefetch must
        # never starve the sync pipeline's staging room), so the PCIe
        # copy rides behind the decode page between staging and admission.
        # seq_id -> (device slices, host length at stage, ring name,
        #            nbytes, transfer cost)
        seq_blob = max(page_blob * max_len
                       // (_pow2(max_active) * _pow2(page_size)), 1)
        self.restore_ring = RingBuffer(restore_ring_bytes or 2 * seq_blob,
                                       _HOST_LINK_BW)
        self._restore_staged: "OrderedDict[int, tuple]" = OrderedDict()
        self.restore_stages = 0         # restores prefetched through the ring
        self.restore_stalls = 0         # prefetches refused: ring had no room
        self.restore_wait_s = 0.0       # h2d restore transfer time (all paths)
        self.restore_stage_hidden_s = 0.0   # portion hidden behind decode
        self.restore_staged_bytes = 0   # cumulative prefetched bytes

    def _put(self, tree):
        """Host -> this engine's device (committed)."""
        return jax.device_put(tree, self.device)

    # ------------------------------------------------------------- protocol
    def clock(self) -> float:
        return time.monotonic()

    def idle_tick(self):
        pass

    def heartbeat(self) -> Optional[Heartbeat]:
        """This round's liveness beat for the scheduler's HealthMonitor.
        A dead or heartbeat-suppressed node yields None — the monitor
        counts the miss and declares failure after ``dead_after`` in a
        row."""
        if self.faults is not None and (
                self.faults.dead or self.faults.heartbeat_suppressed()):
            return None
        return Heartbeat(self.node_id, self.clock(),
                         [DeviceStatus(d) for d in range(self.num_devices)],
                         decode_steps=self.decode_steps,
                         tokens=self.tokens_out)

    def transfer(self, kind: str, fn):
        """Run one risky host transfer through the retry/timeout/dead-
        letter envelope (ExecutionBackend.transfer)."""
        return guarded_transfer(self, kind, fn)

    def acquire_slot(self, co: SequenceCoroutine) -> Optional[int]:
        if self.faults is not None:
            if self.faults.dead:
                return None             # zombie node admits nothing
            if self.faults.oom_active():
                self.oom_rejections += 1
                return None
        if not self.allocator.can_admit(2):
            return None
        for s, owner in enumerate(self.slot_owner):
            if owner is None:
                self.slot_owner[s] = co.seq_id
                self.allocator.alloc(co.seq_id, 2)
                return s
        return None

    def free_slot(self, co: SequenceCoroutine):
        if co.slot is not None and self.slot_owner[co.slot] == co.seq_id:
            self.slot_owner[co.slot] = None
            self.lengths = self.lengths.at[co.slot].set(0)

    def extract_slot(self, co: SequenceCoroutine) -> Dict[str, np.ndarray]:
        self._flush_pending_installs()      # the slot may itself be pending
        s = co.slot
        return {name: np.asarray(leaf[:, s]) for name, leaf in
                self.cache.items()}

    def install_slot(self, co: SequenceCoroutine, slices: Dict[str, np.ndarray]):
        """Stage a COMBINE resume: the cache/token/length writes are held
        host-side and applied by ``_flush_pending_installs`` in ONE jitted
        multi-slot scatter at the next consumer of device state, so a
        refill installing n slots costs one dispatch instead of
        ``n * (leaves + 2)`` eager ones (the remaining eager-dispatch tax
        on slot churn, shared by greedy and sampled paths).  Re-installing
        the same slot overwrites its pending entry."""
        self._pending_install[co.slot] = (slices, int(co.last_token),
                                          int(co.length))
        self.synced_len[co.seq_id] = co.length
        self._install_sampling(co)

    def _pad_slot_arr(self, arr: np.ndarray, leaf) -> np.ndarray:
        """Pad/crop a restored (L, len, ...) slice to the leaf's (L, S, ...)
        slot shape."""
        pad = leaf.shape[2] - arr.shape[1]
        if pad > 0:
            return np.pad(arr, [(0, 0), (0, pad)]
                          + [(0, 0)] * (arr.ndim - 2))
        return arr[:, : leaf.shape[2]]

    def _install_now(self, s: int, slices: Dict[str, np.ndarray],
                     last_token: int, length: int):
        """Eager per-slot install — only for slices missing cache leaves
        (a partial checkpoint must not zero the leaves it omits)."""
        for name, arr in slices.items():
            if name not in self.cache:
                continue
            leaf = self.cache[name]
            a = self._pad_slot_arr(arr, leaf)
            self.cache[name] = leaf.at[:, s].set(
                self._put(np.asarray(a, leaf.dtype)))
        self.tokens = self.tokens.at[s].set(last_token)
        self.lengths = self.lengths.at[s].set(length)

    def _flush_pending_installs(self):
        """Apply all staged slot installs in one jitted batched scatter
        (slot counts pow2-padded by repeating the first entry — duplicate
        identical updates are harmless — so slot churn reuses a handful
        of executables).  Called before anything reads device slot state
        (decode, extract, the sync gather).  Runs in an
        ``engine.node.install`` span; its wall time advances
        ``install_s`` and the slots it writes ``slots_installed``."""
        if not self._pending_install:
            return
        t0 = time.perf_counter()
        with TraceAnnotation("engine.node.install",
                             seqs=[self.slot_owner[s]
                                   for s in self._pending_install]):
            items = list(self._pending_install.items())
            self._pending_install.clear()
            self.slots_installed += self._install_items(items)
            del items       # freeing the staged host copies is install work
        self.install_s += time.perf_counter() - t0

    def _install_items(self, items) -> int:
        """Write ``items`` (slot, staged install) to the device; returns
        the slots written."""
        names = [m[0] for m in self._blob_metas]
        full, partial = [], []
        for s, (slices, tok, ln) in items:
            dst = full if all(nm in slices for nm in names) else partial
            dst.append((s, slices, tok, ln))
        for s, slices, tok, ln in partial:
            self._install_now(s, slices, tok, ln)
        if not full:
            return len(partial)
        n = _pow2(len(full))
        full += [full[0]] * (n - len(full))
        slot_idx = np.array([s for s, *_ in full], np.int32)
        toks = np.array([t for _, _, t, _ in full], np.int32)
        lens = np.array([l for *_, l in full], np.int32)
        upds = {}
        for name, leaf in self.cache.items():
            rows = [np.asarray(self._pad_slot_arr(slices[name], leaf),
                               leaf.dtype) for _, slices, _, _ in full]
            upds[name] = np.stack(rows, axis=1)     # (L, n, S, *trail)

        fn = _lru_get(self._install_cache, n, _INSTALL_JIT_CAP,
                      lambda: jax.jit(_install_scatter,
                                      donate_argnums=(0, 1, 2)), self)
        try:
            out = self.transfer("install", lambda: fn(
                self.cache, self.tokens, self.lengths,
                *self._put((slot_idx, upds, toks, lens))))
        except TransferDeadLetter:
            # the staged installs are lost and their slots hold stale
            # data; the scheduler sees ``dead_lettered`` and escalates to
            # NODE_FAILURE, whose recovery recomputes the affected
            # sequences from their prompts
            return len(partial)
        self.cache, self.tokens, self.lengths = out
        return len(items)

    def _install_sampling(self, co: SequenceCoroutine):
        """Bind a slot's sampling params + re-derived device state.

        The PRNG position is just len(generated) (keys are fold_in(base,
        t), not a split chain) and penalty counts are bincounts of the
        coroutine's tokens, so a coroutine arriving via COMBINE or MIGRATE
        resumes its sampled stream exactly where it left off — no device
        sampling state ever crosses nodes.  Greedy-default sequences only
        reset the slot's params row (a stale sampled row must not make the
        sampled megastep draw for them); their state rows are don't-care
        (temperature<=0 takes the argmax branch), so the O(V) count
        derivation and device scatters are skipped on the slot-churn hot
        path of all-greedy workloads.  The derivation itself is pure
        numpy; the device scatter is deferred to the next sampled page
        (``_flush_pending_sampling``) so a refill installing n slots
        costs ONE batched update instead of 4n eager dispatches."""
        s = co.slot
        row = smp.pack_params([co.sampling], [co.seq_id])
        for k in self._sp_host:
            self._sp_host[k][s] = row[k][0]
        self._sp_dev = None             # host mirror dirty; re-upload lazily
        if co.sampling.is_greedy_default:
            self._pending_smp.pop(s, None)
            return
        V = self._sample_state["counts"].shape[1]
        st_row = smp.init_state(row["seed"], [co.prompt], [co.generated], V)
        self._pending_smp[s] = (smp.base_keys_host(st_row["seed"])[0],
                                st_row["gen_count"][0], st_row["counts"][0],
                                st_row["prompt_counts"][0])

    def _flush_pending_sampling(self):
        """Apply all staged slot-install sampling rows to the device
        state in ONE jitted batched scatter (row counts are pow2-padded
        by repeating the first row — duplicate identical updates are
        harmless — so slot churn reuses a handful of executables)."""
        if not self._pending_smp:
            return
        slots = list(self._pending_smp)
        rows = list(self._pending_smp.values())
        self._pending_smp.clear()
        n = _pow2(len(slots))
        slots += [slots[0]] * (n - len(slots))
        rows += [rows[0]] * (n - len(rows))
        cols = [np.stack([r[i] for r in rows]) for i in range(4)]

        def make():
            def _apply(state, sl, bk, gc, cnt, pc):
                return {"base_key": state["base_key"].at[sl].set(bk),
                        "gen_count": state["gen_count"].at[sl].set(gc),
                        "counts": state["counts"].at[sl].set(cnt),
                        "prompt_counts":
                            state["prompt_counts"].at[sl].set(pc)}
            return jax.jit(_apply, donate_argnums=(0,))
        fn = _lru_get(self._flush_cache, n, 8, make, self)
        self._sample_state = fn(self._sample_state,
                                *self._put((np.asarray(slots, np.int32),
                                            *cols)))

    def _sp_device(self) -> Dict:
        """Packed per-slot sampling params as device arrays (cached until
        a slot install dirties the host mirror)."""
        if self._sp_dev is None:
            self._sp_dev = self._put({k: v for k, v in self._sp_host.items()
                                      if k != "seed"})
        return self._sp_dev

    def reconfigure_partition(self, co: SequenceCoroutine, group: List[int]):
        # On TPU: re-lower the decode step over the group mesh (sequence-
        # split KV).  Single-host CPU: bookkeeping only; the cluster
        # simulator models the speedup (runtime/cluster.py).
        pass

    # ------------------------------------------------------------- transfers
    def _to_host(self, arr) -> np.ndarray:
        """Single funnel for device→host copies (spy point for tests)."""
        self.d2h_transfers += 1
        return np.asarray(arr)

    # ------------------------------------------------------------- compute
    def decode_page(self, active: Sequence[SequenceCoroutine], P: int):
        """Decode up to P tokens for every active sequence.

        Fused path (default): jitted scan(s) totalling exactly
        ``min(P, max remaining)`` steps — the done mask inside the scan
        handles mid-page finishes, and capping the page at the max
        remaining IS the early page exit (no token is ever decoded past a
        slot's budget).  The per-page ``decode_steps`` counter advances by
        the logical step count, same as the per-token loop, so
        simulator/roofline accounting is unchanged."""
        if self.faults is not None and self.faults.dead:
            return                      # zombie: no compute until failover
        self._flush_pending_installs()
        if not active:
            return
        steps = min(P, max(c.remaining for c in active))
        if steps <= 0:
            return
        if self.faults is not None and self.faults.straggler_factor() > 1.0:
            # a real node can't be slowed deterministically — count the
            # affected steps so tests/telemetry see the straggler window
            self.straggler_steps += steps
        tot0 = sum(len(c.generated) for c in active)
        sampled = any(not c.sampling.is_greedy_default for c in active)
        want_lp = [c for c in active if c.logprobs]
        lp_k = max(c.top_logprobs for c in want_lp) if want_lp else None
        if not self.fused and not sampled:
            self._decode_page_looped(active, P, lp_k)
            self._account_progress(active, tot0)
            return
        flags = sp = None
        if sampled:
            with TraceAnnotation("engine.node.sampling_state"):
                # static sampling plan (backend / penalty skip / sort
                # tier) decided host-side from the active params — part
                # of the jit cache key
                flags = smp.flags_for([c.sampling for c in active],
                                      T.padded_vocab(self.cfg))
                self.sample_tier_pages[flags.tier] += 1
                sp = self._sp_device()
                self._flush_pending_sampling()
        # exact step count via pow2 decomposition (40 -> 32+8): each chunk
        # is a cached scan executable (≤ log2(P) distinct sizes), chunks
        # chain on device, blocks concatenate on device -> no masked tail
        # compute and still ONE host transfer for the whole page.  When
        # any active sequence carries non-default SamplingParams the same
        # loop runs the sampled megastep variant: the per-slot sampling
        # state (fold_in PRNG position, penalty counts) rides the scan
        # carry and stop-token hits mask slots on device.  Non-fused
        # sampled (baseline): chunk size 1, one transfer per token.
        with TraceAnnotation("engine.node.megastep", steps=steps,
                             tier=flags.tier if sampled else "greedy"):
            rem = np.zeros((self.max_active,), np.int32)
            for co in active:
                rem[co.slot] = co.remaining
            rem_j = self._put(rem)
            state = self._sample_state
            blocks = []
            left = steps
            while left > 0:
                chunk = (1 << (left.bit_length() - 1)) if self.fused else 1
                if self.module_rt is not None:
                    out = self.module_rt.forward_decode_page(
                        self.tokens, self.cache, self.lengths, rem_j,
                        self.b_attn, chunk,
                        sampling=(sp, state) if sampled else None,
                        lp_k=lp_k, flags=flags)
                else:
                    mega = self._get_megastep(chunk, sampled, lp_k, flags)
                    args = (self.params, self.cache, self.tokens,
                            self.lengths, rem_j) + ((sp, state) if sampled
                                                    else ())
                    out = mega(*args)
                if sampled:
                    (blk, self.tokens, self.lengths, rem_j, self.cache,
                     state) = out
                else:
                    blk, self.tokens, self.lengths, rem_j, self.cache = out
                blocks.append(blk if self.fused else self._to_host(blk))
                left -= chunk
            if sampled:
                self._sample_state = state
            self.decode_steps += steps
            if self.fused:
                block = (blocks[0] if len(blocks) == 1
                         else jnp.concatenate(blocks))
        if self.fused:
            with TraceAnnotation("engine.node.block_wait"):
                block_np = self._to_host(block)  # the ONE d2h per page
        else:
            block_np = np.concatenate(blocks)
        with TraceAnnotation("engine.node.apply_block"):
            self._apply_block(active, block_np, steps)
            self._account_progress(active, tot0)

    def _account_progress(self, active: Sequence[SequenceCoroutine],
                          tot0: int) -> None:
        """Advance the heartbeat progress counter by this page's emitted
        tokens.  An injected straggler divides the credit by its factor —
        the engine cannot actually run slower, so the fault is honored at
        the observation boundary: the node's beats under-report progress
        exactly as a real 4x-slow node's would."""
        emitted = sum(len(c.generated) for c in active) - tot0
        f = 1.0
        if self.faults is not None:
            f = max(self.faults.straggler_factor(), 1.0)
        self.tokens_out += emitted / f

    def _apply_block(self, active: Sequence[SequenceCoroutine], block_np,
                     steps: int):
        """Apply a (steps, max_active) token block — or the packed
        (steps, max_active, 2+2K) logprob plane — to coroutine state,
        truncating at each sequence's first stop-token hit (the stop token
        is emitted, then the sequence halts — mirroring the on-device
        remaining-zeroing)."""
        lp_np = topv = topi = None
        if block_np.ndim == 3:
            toks_np, lp_np, topv, topi = T.unpack_logprob_block(block_np)
        else:
            toks_np = block_np
        for co in active:
            n = min(steps, co.remaining)
            if n <= 0:
                continue
            toks, hit = co.sampling.truncate_at_stop(toks_np[:n, co.slot])
            co.stopped = co.stopped or hit
            co.generated.extend(toks)
            co.last_token = toks[-1]
            co.length += len(toks)
            if co.logprobs and lp_np is not None:
                self._append_logprobs(
                    co, [float(x) for x in lp_np[:len(toks), co.slot]],
                    None if topv is None else topv[:len(toks), co.slot],
                    None if topi is None else topi[:len(toks), co.slot])

    @staticmethod
    def _append_logprobs(co: SequenceCoroutine, chosen, topv, topi):
        """Append one block of chosen-token logprobs (and the requested
        top-K alternatives) aligned with the tokens just applied."""
        co.token_logprobs.extend(chosen)
        if co.top_logprobs and topv is not None:
            k = co.top_logprobs
            for t in range(len(chosen)):
                co.top_token_logprobs.append(
                    [(int(topi[t][j]), float(topv[t][j])) for j in range(k)])

    def _get_megastep(self, steps: int, sampled: bool = False, lp_k=None,
                      flags=None):
        def make():
            if sampled:
                def _mega(params, cache, tokens, lengths, remaining, sp,
                          state):
                    return T.decode_page(self.cfg, self.axes, params, cache,
                                         tokens, lengths, remaining, steps,
                                         sampling=(sp, state), lp_k=lp_k,
                                         flags=flags)
            else:
                def _mega(params, cache, tokens, lengths, remaining):
                    return T.decode_page(self.cfg, self.axes, params, cache,
                                         tokens, lengths, remaining, steps,
                                         lp_k=lp_k)
            return jax.jit(_mega, donate_argnums=(1,))
        return _lru_get(self._megastep_cache, (steps, sampled, lp_k, flags),
                        _MEGASTEP_JIT_CAP, make, self)

    def _get_prefill_sampler(self, n: int, flags):
        """Jitted first-token draw (keys = fold_in(base, 0)); without the
        cache every prefill re-traced the eagerly-vmapped sampler, which
        cost more than the prefill forward itself."""
        def make():
            def _draw(logits2d, pcounts, counts, sp_rows, base):
                keys = smp.step_keys(base, jnp.zeros((n,), jnp.int32))
                return smp.sample(logits2d, pcounts, counts, sp_rows, keys,
                                  flags)
            return jax.jit(_draw)
        return _lru_get(self._prefill_sample_cache, (n, flags),
                        _PREFILL_JIT_CAP, make, self)

    def _decode_page_looped(self, active: Sequence[SequenceCoroutine],
                            P: int, lp_k=None):
        """Seed per-token loop: one jitted step, one host round-trip and
        Python bookkeeping per token.  Kept as the measured baseline for
        benchmarks/decode_throughput.py (fused=False).  With ``lp_k`` the
        raw logits cross per token instead and the logprobs are computed
        host-side (baseline only; the fused path packs them on device)."""
        by_slot = {c.slot: c for c in active}
        steps = min(P, max(c.remaining for c in active))
        if (lp_k is not None and self.module_rt is None
                and self._decode_logits is None):
            self._decode_logits = jax.jit(
                lambda p, c, t, l: T.decode_step_logits(
                    self.cfg, self.axes, p, c, t, l), donate_argnums=(1,))
        for _ in range(steps):
            lp_np = None
            if lp_k is not None:
                if self.module_rt is not None:
                    logits, self.cache = self.module_rt.forward_decode(
                        self.tokens, self.cache, self.lengths, self.b_attn,
                        want_logits=True)
                else:
                    logits, self.cache = self._decode_logits(
                        self.params, self.cache, self.tokens, self.lengths)
                logits_np = self._to_host(logits)
                lp_np = _np_log_softmax(logits_np)
                nxt_np = np.argmax(logits_np, axis=-1).astype(np.int32)
            elif self.module_rt is not None:
                nxt, self.cache = self.module_rt.forward_decode(
                    self.tokens, self.cache, self.lengths, self.b_attn)
                nxt_np = None
            else:
                nxt, self.cache = self._decode(self.params, self.cache,
                                               self.tokens, self.lengths)
                nxt_np = None
            self.decode_steps += 1
            if nxt_np is None:
                nxt_np = self._to_host(nxt)
            upd_tok, upd_len = [], []
            for s, co in by_slot.items():
                if co.remaining > 0:
                    tok = int(nxt_np[s])
                    co.generated.append(tok)
                    co.last_token = tok
                    co.length += 1
                    if co.logprobs and lp_np is not None:
                        topv = topi = None
                        if co.top_logprobs:
                            topi = [_np_top_k_idx(lp_np[s],
                                                  co.top_logprobs)]
                            topv = [lp_np[s][topi[0]]]
                        self._append_logprobs(
                            co, [float(lp_np[s, tok])], topv, topi)
                    upd_tok.append((s, tok))
                    upd_len.append((s, co.length))
            if upd_tok:
                idx, tok, ln = self._put((
                    np.array([s for s, _ in upd_tok], np.int32),
                    np.array([t for _, t in upd_tok], np.int32),
                    np.array([l for _, l in upd_len], np.int32)))
                self.tokens = self.tokens.at[idx].set(tok)
                self.lengths = self.lengths.at[idx].set(ln)
            if all(c.remaining == 0 for c in active):
                break

    def sync_appends(self, active: Sequence[SequenceCoroutine]):
        """Blocking host-KV sync (§5.3 i): stage + drain in one call —
        the seed path's gather-transfer-append, kept for direct callers
        and as the measured baseline of the ``--overlap`` benchmark."""
        self.stage_appends(active)
        self.drain_appends()

    def _get_gather(self, n: int, W: int):
        """Jitted batched dirty-window gather -> one (L, n, W, F_total)
        blob, bucketed to (pow2 slots, pow2 window) so steady-state page
        syncs reuse a handful of executables."""
        names = tuple(m[0] for m in self._blob_metas)
        return _lru_get(self._gather_cache, (n, W), _GATHER_JIT_CAP,
                        lambda: jax.jit(partial(_gather_blob, names)), self)

    def _gather_dirty(self, active) -> Optional[_InFlightSync]:
        """Issue the batched gather of every dirty slot's [synced, length)
        window and snapshot the per-slot spans; advances ``synced_len`` at
        ISSUE time (the pipeline owns the window from here — a later
        yield checkpoint supersedes it with identical values, so a stale
        drain is a harmless rewrite)."""
        assert len({leaf.dtype for leaf in self.cache.values()}) == 1, \
            "batched gather concatenates leaves: mixed dtypes would be " \
            "silently promoted — add a per-dtype blob before relaxing this"
        self._flush_pending_installs()
        todo = []
        for co in active:
            if co.slot is None:
                continue
            start = self.synced_len.get(co.seq_id, 0)
            first = not self.host_store.has(co.seq_id)
            if first:
                start = 0               # first sync: checkpoint from zero
            if co.length > start:
                todo.append((co, start, first))
        if not todo:
            return None
        n, W = len(todo), int(max(co.length - start
                                  for co, start, _ in todo))
        n_pad, W_pad = _pow2(n), _pow2(W)
        pad = [todo[0]] * (n_pad - n)
        slots = np.array([[co.slot] for co, _, _ in todo + pad], np.int32)
        starts = np.array([start for _, start, _ in todo + pad])
        pos = np.minimum(starts[:, None] + np.arange(W_pad)[None],
                         self.max_len - 1).astype(np.int32)
        blob = self._get_gather(n_pad, W_pad)(
            self.cache, *self._put((slots, pos)))
        snaps = []
        for co, start, first in todo:
            snaps.append((co.seq_id, start, co.length - start, first))
            self.synced_len[co.seq_id] = co.length
        self._sync_tag += 1
        nbytes = int(np.prod(blob.shape)) * blob.dtype.itemsize
        return _InFlightSync(blob, self._blob_metas, snaps, nbytes,
                             f"sync{self._sync_tag}")

    def stage_appends(self, active: Sequence[SequenceCoroutine]):
        """Issue the page's dirty-window KV gather and start its async
        device→host copy; the blob rides the ring buffer until
        ``drain_appends`` lands it.  With ``overlap=False`` (or when the
        blob cannot fit the ring even after a forced drain) this degrades
        to the blocking synchronous path.  Runs in an
        ``engine.node.gather`` span."""
        with TraceAnnotation("engine.node.gather"):
            ent = self._gather_dirty(active)
            if ent is None:
                return
            if not self.overlap:
                self._materialize(ent)
                return
            if not self.ring.can_fit(ent.nbytes):
                # backpressure: land everything in flight, then retry the
                # reservation — the stall the plan optimizer sizes
                # ring_buffer_bytes against
                self.sync_stalls += 1
                self.drain_appends()
            if self.ring.can_fit(ent.nbytes):
                try:
                    self.transfer("stage", ent.blob.copy_to_host_async)
                except TransferDeadLetter:
                    self._abandon_blob(ent)
                    return
                self.ring.reserve(ent.name, ent.nbytes)
                self._inflight.append(ent)
                self.sync_stages += 1
            else:
                self._materialize(ent)  # blob larger than the whole ring

    def drain_appends(self, keep_newest: int = 0):
        """Land staged blobs in the host store, oldest first.  The
        scheduler's SYNC_DRAIN phase keeps the newest blob in flight
        (``keep_newest=1``) so its copy rides behind the next megastep;
        every consumer of host-store state (evict / migrate / failure
        recovery) forces a full drain first."""
        while len(self._inflight) > keep_newest:
            ent = self._inflight.popleft()
            self.ring.release(ent.name)
            self._materialize(ent)

    def _materialize(self, ent: _InFlightSync):
        """Blocking half of the pipeline: wait for the blob's copy (the
        ONE host transfer for the page's KV) and append it page-by-page
        into the host store.  Runs in an ``engine.node.materialize``
        span."""
        with TraceAnnotation("engine.node.materialize", blob=ent.name):
            t0 = time.perf_counter()
            try:
                blob = self.transfer("drain",
                                     lambda: self._to_host(ent.blob))
            except TransferDeadLetter:
                self._abandon_blob(ent)
                return
            finally:
                self.sync_wait_s += time.perf_counter() - t0
            offs, off = {}, 0
            for name, trail, f in ent.metas:
                offs[name] = (off, off + f)
                off += f
            L = blob.shape[0]
            for i, (seq_id, start, n, first) in enumerate(ent.snaps):
                if not first and not self.host_store.has(seq_id):
                    continue    # evicted since the gather: stays dropped
                slices = {}
                for name, trail, _ in ent.metas:
                    lo, hi = offs[name]
                    slices[name] = blob[:, i, :n, lo:hi].reshape(
                        (L, n) + trail)
                if self.host_store.has(seq_id):
                    self.host_store.append_tokens(seq_id, slices, start)
                else:
                    self.host_store.checkpoint(seq_id, slices, start + n)

    def _abandon_blob(self, ent: _InFlightSync):
        """A staged blob was lost to a dead-lettered transfer.  Its
        sequences' host checkpoints now lag their coroutines' generated
        streams, so a later migrate would resume from CORRUPT state —
        drop their host-store entries entirely; the NODE_FAILURE recovery
        this dead-letter escalates to will recompute them from their
        prompts (bitwise-identical tokens, §5.6)."""
        self.abandoned_blobs += 1
        for seq_id, _start, _n, _first in ent.snaps:
            if self.host_store.has(seq_id):
                self.host_store.drop(seq_id)
            self.synced_len.pop(seq_id, None)

    # ------------------------------------- staged h2d restores (governor)
    def stage_restore(self, co: SequenceCoroutine) -> bool:
        """Prefetch a suspended sequence's host checkpoint toward the
        device (async ``device_put`` per cache leaf) behind a ring-buffer
        reservation — the h2d mirror of ``stage_appends``.  The copy
        overlaps the decode page(s) between now and the sequence's
        COMBINE, where ``take_restore`` consumes it without PCIe wait.
        Returns True when a restore is staged for the sequence
        (pre-existing counts); False when it cannot be (no host state, or
        the ring has no room — counted in ``restore_stalls``, the same
        backpressure signal the d2h pipeline uses)."""
        ent = self._restore_staged.get(co.seq_id)
        if ent is not None:
            if (self.host_store.has(co.seq_id)
                    and self.host_store.seqs[co.seq_id].length == ent[1]):
                return True
            self.discard_restore(co.seq_id)     # stale: checkpoint advanced
        if not self.host_store.has(co.seq_id):
            return False
        with TraceAnnotation("engine.node.restore", seq=co.seq_id):
            t0 = time.perf_counter()
            slices = self.host_store.restore(co.seq_id, self.max_len)
            nbytes = sum(int(np.asarray(v).nbytes)
                         for v in slices.values())
            if not self.restore_ring.can_fit(nbytes):
                self.restore_stalls += 1
                return False
            try:
                dev = self.transfer("restore", lambda: self._put(slices))
            except TransferDeadLetter:
                return False
            self.restore_ring.reserve(f"restore{co.seq_id}", nbytes)
            self._restore_staged[co.seq_id] = (
                dev, self.host_store.seqs[co.seq_id].length,
                f"restore{co.seq_id}", nbytes, time.perf_counter() - t0)
            self.restore_stages += 1
            self.restore_staged_bytes += nbytes
            return True

    def restore_ready(self, seq_id: int) -> bool:
        """True when the sequence's staged restore has drained: a live
        (non-stale) prefetch is in the ring, so COMBINE's ``take_restore``
        installs it without a synchronous PCIe wait."""
        ent = self._restore_staged.get(seq_id)
        return (ent is not None and self.host_store.has(seq_id)
                and self.host_store.seqs[seq_id].length == ent[1])

    def take_restore(self, seq_id: int) -> Optional[Dict]:
        """Consume a staged restore for COMBINE.  A prefetch whose source
        checkpoint advanced since staging is stale and discarded (the
        append pipeline drained new pages into the host store) — the
        restore then falls back to the synchronous path.  Returns None
        only when the sequence has no host state at all."""
        ent = self._restore_staged.pop(seq_id, None)
        cur = (self.host_store.seqs[seq_id].length
               if self.host_store.has(seq_id) else None)
        if ent is not None:
            dev, length, name, nbytes, cost = ent
            self.restore_ring.release(name)
            if cur is not None and cur == length:
                # the device_put overlapped the pages decoded since it
                # was staged: its transfer time was hidden behind compute
                self.restore_wait_s += cost
                self.restore_stage_hidden_s += cost
                return dev
        if cur is None:
            return None
        with TraceAnnotation("engine.node.restore", seq=seq_id):
            t0 = time.perf_counter()
            slices = self.host_store.restore(seq_id, self.max_len)
            self.restore_wait_s += time.perf_counter() - t0
        return slices

    def discard_restore(self, seq_id: int) -> None:
        """Drop one staged restore and release its ring reservation
        (MIGRATE moved the state to another node, or it went stale)."""
        ent = self._restore_staged.pop(seq_id, None)
        if ent is not None:
            self.restore_ring.release(ent[2])

    def discard_restores(self) -> None:
        """Drop every staged restore (NODE_FAILURE teardown: the target
        devices are gone; the ring was already reset)."""
        for seq_id in list(self._restore_staged):
            self.discard_restore(seq_id)

    def prefill(self, cos: Sequence[SequenceCoroutine]):
        """Prefill a batch of INIT coroutines; leaves them INACTIVE with KV
        checkpointed to the host store (paper Fig. 7 prefill flow).

        Executables are bucketed to (pow2 batch, pow2 sequence) and held in
        a small LRU so long mixed workloads can't accumulate one jit per
        exact (B, S).

        With the prefix index enabled the batch is first deduplicated by
        prompt: a fork fan-out (or identical duplicate submits) forwards
        the prompt ONCE and every sibling samples its first token from the
        lead's logits row with its own seed — bitwise-identical to
        independent submissions because the forward and the sampler are
        row-wise and the first-token key is fold_in(PRNGKey(seed), 0) per
        row.  A lead whose leading full pages already sit in the index
        (cross-submit hit) skips their forward entirely: the span's host
        pages are grafted into a dense cache and only the prompt tail is
        teacher-forced through the decode step, which reproduces the full
        prefill's last-position logits and cache bitwise (decode and
        prefill share one attention implementation)."""
        if self.faults is not None and self.faults.dead:
            return          # zombie: coroutines stay INIT for recovery
        if not cos:
            return
        idx = self.host_store.prefix_index
        # prompt-identical groups (a fork fan-out arrives as one group); a
        # disabled index degrades to singleton groups == the PR 1-7 path
        groups: "OrderedDict[tuple, List[SequenceCoroutine]]" = OrderedDict()
        lead_of: Dict[int, int] = {}
        for c in cos:
            key = tuple(c.prompt) if idx is not None else ("seq", c.seq_id)
            groups.setdefault(key, []).append(c)
        for group in groups.values():
            for c in group:
                lead_of[c.seq_id] = group[0].seq_id
        leads = [g[0] for g in groups.values()]
        names = list(self.cache.keys())
        P = self.host_store.page_size
        # cross-submit hits: cap the reuse at the last full page BEFORE the
        # final prompt position — the last position must be recomputed to
        # produce the first-token logits
        hits: Dict[int, list] = {}
        fresh: List[SequenceCoroutine] = []
        for lead in leads:
            chain = []
            if idx is not None:
                chain = idx.match(lead.prompt)[: (lead.prompt_len - 1) // P]
                if chain and not all(all(nm in nd.pages for nm in names)
                                     for nd in chain):
                    chain = []      # span missing a cache leaf: recompute
            if chain:
                hits[lead.seq_id] = chain
            else:
                fresh.append(lead)

        lead_rows: Dict[int, object] = {}   # lead seq_id -> (V,) logits row
        fresh_logits = None
        if fresh:
            with TraceAnnotation("engine.node.prefill_forward",
                                 seqs=[c.seq_id for c in fresh]):
                fresh_logits = self._prefill_fresh(fresh, lead_rows)
        if hits:
            with TraceAnnotation("engine.node.prefix_graft",
                                 seqs=list(hits)):
                self._graft_prefix_hits(leads, hits, lead_rows)

        # publish every lead's prompt pages (dedupes to canonical frozen
        # spans), then bind fork siblings to the lead's span COW
        if idx is not None:
            for group in groups.values():
                lead = group[0]
                self.host_store.publish_prefix(lead.seq_id, lead.prompt)
                for sib in group[1:]:
                    self.host_store.clone_shared(lead.seq_id, sib.seq_id)
                    sib.prefix_hit_tokens = sib.prompt_len
                    self.prefill_tokens_saved += sib.prompt_len

        with TraceAnnotation("engine.node.first_token"):
            self._first_tokens(cos, fresh, fresh_logits, lead_rows, lead_of)
        f = 1.0
        if self.faults is not None:
            f = max(self.faults.straggler_factor(), 1.0)
        self.tokens_out += len(cos) / f     # one first token per sequence

    def _prefill_fresh(self, fresh: List[SequenceCoroutine],
                       lead_rows: Dict[int, object]):
        """The bucketed prompt forward of the leads with no prefix hit,
        their prompt KV checkpointed to the host store; fills
        ``lead_rows`` and returns the batch's last-position logits."""
        maxlen = max(c.prompt_len for c in fresh)
        S = max(_pow2(maxlen), 8)         # pow2 sequence bucket
        B = max(_pow2(len(fresh)), 1)     # pow2 batch bucket (padded)
        toks = np.zeros((B, S), np.int32)  # left-align, pad after
        last_idx = np.zeros((B,), np.int32)
        for i, c in enumerate(fresh):
            toks[i, : c.prompt_len] = c.prompt[:]
            last_idx[i] = c.prompt_len - 1
        fn = _lru_get(self._prefill_cache, (B, S), _PREFILL_JIT_CAP,
                      lambda: jax.jit(partial(_prefill_logits, self.cfg,
                                              self.axes)), self)
        fresh_logits, cache = fn(self.params,
                                 *self._put((toks, last_idx)))
        nf = len(fresh)
        # batched host-checkpoint gather: flatten every leaf's first-nf
        # rows into ONE (L, nf, W, F_total) blob and move it with a
        # single host transfer (the per-sequence/per-leaf slicing this
        # replaces paid n_seqs * n_leaves small copies per batch)
        W = maxlen
        assert len({leaf.dtype for leaf in cache.values()}) == 1, \
            "batched gather concatenates leaves: mixed dtypes would " \
            "be silently promoted — add a per-dtype blob before " \
            "relaxing this"
        metas, parts = [], []
        for name, leaf in cache.items():
            seg = leaf[:, :nf, :W]              # (L, nf, W, *trail)
            trail = seg.shape[3:]
            metas.append((name, trail,
                          int(np.prod(trail)) if trail else 1))
            parts.append(seg.reshape(seg.shape[0], nf, W, -1))
        blob = self._to_host(jnp.concatenate(parts, axis=-1))
        offs, off = {}, 0
        for name, trail, f in metas:
            offs[name] = (off, off + f)
            off += f
        L = blob.shape[0]
        for i, lead in enumerate(fresh):
            pl = lead.prompt_len
            slices = {}
            for name, trail, _ in metas:
                lo, hi = offs[name]
                slices[name] = blob[:, i, :pl, lo:hi].reshape(
                    (L, pl) + trail)
            self.host_store.checkpoint(lead.seq_id, slices, pl)
            lead_rows[lead.seq_id] = fresh_logits[i, 0, :]
            self.prefill_tokens += pl
        return fresh_logits

    def _graft_prefix_hits(self, leads: List[SequenceCoroutine],
                           hits: Dict[int, list],
                           lead_rows: Dict[int, object]):
        """Prefix-hit leads: graft the span's host pages into a dense
        cache and teacher-force only the tail (at most one page + the
        partial block) through the decode step."""
        names = list(self.cache.keys())
        P = self.host_store.page_size
        for lead in leads:
            chain = hits.get(lead.seq_id)
            if chain is None:
                continue
            m = len(chain) * P
            pl = lead.prompt_len
            self.host_store.attach_shared(lead.seq_id, chain)
            S = max(_pow2(pl), 8)
            with jax.default_device(self.device):
                dense = self._put(T.init_cache(self.cfg, 1, S))
            for name in names:
                seg = np.concatenate([nd.pages[name] for nd in chain],
                                     axis=1)        # (L, m, *trail)
                dense[name] = dense[name].at[:, :, :m].set(
                    self._put(seg)[:, None])
            if self._decode_logits is None:
                self._decode_logits = jax.jit(
                    lambda p, c, t, l: T.decode_step_logits(
                        self.cfg, self.axes, p, c, t, l),
                    donate_argnums=(1,))
            row = None
            for t in range(m, pl):
                row, dense = self._decode_logits(
                    self.params, dense,
                    *self._put((np.asarray([lead.prompt[t]], np.int32),
                                np.asarray([t], np.int32))))
            slices = {name: self._to_host(dense[name][:, 0, m:pl])
                      for name in names}
            self.host_store.append_tokens(lead.seq_id, slices, m)
            lead_rows[lead.seq_id] = row[0]
            lead.prefix_hit_tokens = m
            self.prefill_tokens += pl - m
            self.prefill_tokens_saved += m

    def _first_tokens(self, cos: Sequence[SequenceCoroutine],
                      fresh: List[SequenceCoroutine], fresh_logits,
                      lead_rows: Dict[int, object],
                      lead_of: Dict[int, int]):
        """Draw each sequence's first token from its lead's logits row
        (with its log-probs when asked) and leave it INACTIVE."""
        n = len(cos)
        if fresh_logits is not None and len(fresh) == n:
            logits2d = fresh_logits[:n, 0, :]   # no dedupe/hit: batch rows
        else:
            logits2d = jnp.stack([lead_rows[lead_of[c.seq_id]] for c in cos])
        # first generated token: device-sampled when any sequence asks for
        # it (key = fold_in(PRNGKey(seed), 0), counts over the prompt);
        # all-greedy batches keep the host argmax
        logits_np = None
        if any(not c.sampling.is_greedy_default for c in cos):
            sp = smp.pack_params([c.sampling for c in cos],
                                 [c.seq_id for c in cos])
            st = smp.init_state(sp["seed"], [list(c.prompt) for c in cos],
                                [[] for _ in cos],
                                T.padded_vocab(self.cfg))
            flags = smp.flags_for([c.sampling for c in cos],
                                  T.padded_vocab(self.cfg))
            draw = self._get_prefill_sampler(n, flags)
            first = self._to_host(draw(logits2d, *self._put((
                st["prompt_counts"], st["counts"],
                {k: sp[k] for k in _SAMPLE_ROW_KEYS},
                smp.base_keys_host(st["seed"])))))
        else:
            logits_np = self._to_host(logits2d)
            first = np.argmax(logits_np, axis=-1)
        lp_np = None
        if any(c.logprobs for c in cos):
            if logits_np is None:       # sampled batch: logits still on dev
                logits_np = self._to_host(logits2d)
            lp_np = _np_log_softmax(logits_np)
        for i, co in enumerate(cos):
            pl = co.prompt_len
            co.last_token = int(first[i])
            co.generated.append(co.last_token)
            if co.logprobs and lp_np is not None:
                topv = topi = None
                if co.top_logprobs:
                    topi = [_np_top_k_idx(lp_np[i], co.top_logprobs)]
                    topv = [lp_np[i][topi[0]]]
                self._append_logprobs(
                    co, [float(lp_np[i, co.last_token])], topv, topi)
            if co.last_token in co.sampling.stop:
                co.stopped = True
            co.length = pl
            co.phase = Phase.DECODING
            co.status = Status.INACTIVE
            self.synced_len[co.seq_id] = pl


# NodeEngine declares conformance to the formal backend contract; the
# scheduler re-validates instances (including the data members created in
# __init__) at construction.
validate_backend(NodeEngine)
