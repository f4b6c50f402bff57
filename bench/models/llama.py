"""The Llama block family (Qwen2, SmolLM): seeded weights, the binding
to the program's parameter layout, the plain float32 reference, and the
least work of its serving steps (``work``).

The reference imports nothing of the program.  It follows the published
architecture: token embedding; per layer a pre-norm RMSNorm, q/k/v
projections (with biases where ``qkv_bias``), rotate-half RoPE, causal
grouped-query attention, the output projection, a second RMSNorm and a
SwiGLU MLP, each added to the residual; a final RMSNorm; and the head,
which is the tied embedding.  It runs in float32 at ``highest`` matmul
precision, one sequence at a time, and returns per-position statistics
rather than logits, so that a 150k-entry vocabulary never has to sit in
memory for a whole sequence.

Weights are drawn from the seed by ``plain_weights``, in bf16, the type
they are served in.  ``program_weights`` makes the program's copy in one
jitted call; the reference draws its own copy again from the same seed
after the window, so it takes nothing the program made.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import flops

HEAD_CHUNK = 256        # positions per block of the head's logits

# How the plain weights are drawn (the published configs give only
# ``initializer_range`` for matrices, and ones/zeros for norms/biases):
# matrices and biases ~ N(0, initializer_range); norm scales ~ 1 + N(0,
# NORM_SPREAD), so the comparison covers the bias and norm-scale paths.
NORM_SPREAD = 0.1


def shapes(c: dict) -> dict:
    """Layer-stacked shapes of the plain weights, (in, out) per matrix."""
    L, D, F, V = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or D // H
    s = {"embed": (V, D), "norm": (D,),
         "ln1": (L, D), "ln2": (L, D),
         "wq": (L, D, H * dh), "wk": (L, D, Hkv * dh), "wv": (L, D, Hkv * dh),
         "wo": (L, H * dh, D),
         "wg": (L, D, F), "wu": (L, D, F), "wd": (L, F, D)}
    if c.get("qkv_bias"):
        s.update(bq=(L, H * dh), bk=(L, Hkv * dh), bv=(L, Hkv * dh))
    return s


def plain_weights(c: dict, key) -> dict:
    """All weights from one key, in bf16 (call under jit)."""
    sd = c["initializer_range"]
    out = {}
    names = sorted(shapes(c).items())
    for k, (name, shp) in zip(jax.random.split(key, len(names)), names):
        z = jax.random.normal(k, shp, jnp.float32)
        if name in ("norm", "ln1", "ln2"):
            w = 1.0 + NORM_SPREAD * z
        else:
            w = sd * z
        out[name] = w.astype(jnp.bfloat16)
    return out


def key_from_seed(seed: int):
    """A PRNG key from any whole seed (wider than 32 bits included)."""
    words = np.random.SeedSequence(int(seed) & (2 ** 128 - 1)
                                   ).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# ---------------------------------------------------------------- binding
def program_config(c: dict, base):
    """The program's ModelConfig with every size taken from the file."""
    import dataclasses
    D, H = c["hidden_size"], c["num_attention_heads"]
    return dataclasses.replace(
        base, num_layers=c["num_hidden_layers"], d_model=D, num_heads=H,
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c.get("head_dim") or D // H,
        attn_bias=bool(c.get("qkv_bias")), rope_theta=c["rope_theta"],
        dtype=c["torch_dtype"])


def to_program(c: dict, w: dict) -> dict:
    """The plain weights in the program's parameter tree (per layer
    ``ln1``/``attn``/``ln2``/``mlp``, heads split out of the projection
    matrices, a separate ``lm_head`` that is the tied embedding)."""
    L, D = c["num_hidden_layers"], c["hidden_size"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or D // H
    if c["vocab_size"] % 16:
        raise ValueError("the program pads the vocabulary to a multiple of "
                         "16; this binding does not")
    attn = {"wq": w["wq"].reshape(L, D, H, dh),
            "wk": w["wk"].reshape(L, D, Hkv, dh),
            "wv": w["wv"].reshape(L, D, Hkv, dh),
            "wo": w["wo"].reshape(L, H, dh, D)}
    if c.get("qkv_bias"):
        attn.update(bq=w["bq"].reshape(L, H, dh),
                    bk=w["bk"].reshape(L, Hkv, dh),
                    bv=w["bv"].reshape(L, Hkv, dh))
    return {"embed": w["embed"], "lm_head": w["embed"].T,
            "final_norm": {"w": w["norm"]},
            "layers": {"ln1": {"w": w["ln1"]}, "attn": attn,
                       "ln2": {"w": w["ln2"]},
                       "mlp": {"w1": w["wg"], "w3": w["wu"],
                               "w2": w["wd"]}}}


def program_weights(c: dict, seed: int, device):
    """The program's weights on ``device``, made there in one jitted call."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    fn = jax.jit(lambda k: to_program(c, plain_weights(c, k)),
                 out_shardings=sharding)
    return fn(jax.device_put(key_from_seed(seed), sharding))


# ------------------------------------------------------------- least work
def work(c: dict):
    """The least work over a traced stretch, by ``flops.py``'s dense
    block with grouped-query attention at the file's shapes:
    ``count(stretch)`` gives the ``flops.Count`` of the stretch's decode
    steps (each token's K/V at its own attended length) and of its
    prefills.  The stretch does not count batched prefill calls, so the
    prefills' bytes leave out their weight reads; no reader uses them."""
    d = flops.dims(c)

    def count(st) -> flops.Count:
        return flops.Count(
            decode=flops.decode(d, int(st.counters["decode_steps"]),
                                st.decode_tokens, st.context_sum),
            prefill=flops.prefill(d, st.prefill_lens, 0))
    return count


# -------------------------------------------------------------- reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over (S, heads, dh) at positions 0..S-1."""
    S, dh = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def hidden(c: dict, w: dict, tokens):
    """Final-normed hidden states (S, D) of one sequence, float32."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    Hkv = c["num_key_value_heads"]
    dh = c.get("head_dim") or D // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    S = tokens.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    layer_names = ["ln1", "ln2", "wq", "wk", "wv", "wo", "wg", "wu", "wd"]
    if c.get("qkv_bias"):
        layer_names += ["bq", "bk", "bv"]

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q, k, v = h @ lw["wq"], h @ lw["wk"], h @ lw["wv"]
        if c.get("qkv_bias"):
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q = _rope(q.reshape(S, H, dh), theta)
        k = _rope(k.reshape(S, Hkv, dh), theta)
        v = v.reshape(S, Hkv, dh)
        k, v = jnp.repeat(k, H // Hkv, 1), jnp.repeat(v, H // Hkv, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * dh)
        x = x + o @ lw["wo"]
        h = _rms(x, lw["ln2"], eps)
        return x + (jax.nn.silu(h @ lw["wg"]) * (h @ lw["wu"])) @ lw["wd"], None

    x = w["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in layer_names})
    return _rms(x, w["norm"], eps)


def position_stats(c: dict, w: dict, tokens, targets, probes):
    """Per position t of one sequence, from the logits that predict the
    token after t: the best logit, the logit of ``targets[t]`` and of
    ``probes[t]``, the log-sum-exp, and the argmax."""
    h = hidden(c, w, tokens)
    S = tokens.shape[0]
    n = S // HEAD_CHUNK

    def block(args):
        hb, tb, pb = args
        logits = hb @ w["embed"].T
        take = lambda i: jnp.take_along_axis(logits, i[:, None], 1)[:, 0]
        return {"best": logits.max(-1), "at_target": take(tb),
                "at_probe": take(pb),
                "lse": jax.nn.logsumexp(logits, -1),
                "argmax": jnp.argmax(logits, -1).astype(jnp.int32)}

    split = lambda a: a.reshape((n, HEAD_CHUNK) + a.shape[1:])
    out = jax.lax.map(block, (split(h), split(targets), split(probes)))
    return jax.tree.map(lambda a: a.reshape(S), out)


def fp8_weights(w: dict) -> dict:
    """The control's weights: every matrix (and the tied embedding)
    rounded to fp8 (e4m3), the precision below the served bf16, with one
    symmetric scale per output channel, returned in float32."""
    def q(a, axis):
        # e4m3 rounding by reduce_precision (whose e4m3 tops out at 240,
        # having infinities): a cast to float8 and back is a round trip
        # the TPU compiler may drop
        s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True),
                        1e-30) / 240.0
        return jax.lax.reduce_precision(
            a / s, exponent_bits=4, mantissa_bits=3) * s
    out = dict(w)
    for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
        out[name] = q(w[name], axis=-2)     # per output column
    out["embed"] = q(w["embed"], axis=-1)   # per vocabulary row
    return out


class Reference:
    """Float32 reference (and its lower-precision control) for one
    configuration, seeded like the program's weights.  ``stats`` runs one
    sequence padded to ``max_len`` positions."""

    def __init__(self, c: dict, seed: int, device, max_len: int,
                 control: bool = False):
        self.c, self.max_len = c, max_len
        self.device = device
        sharding = jax.sharding.SingleDeviceSharding(device)
        make = jax.jit(lambda k: jax.tree.map(
            lambda a: a.astype(jnp.float32), plain_weights(c, k)),
            out_shardings=sharding)
        self.w = make(jax.device_put(key_from_seed(seed), sharding))
        if control:
            self.w = jax.jit(fp8_weights)(self.w)
        self._fn = jax.jit(partial(position_stats, c))

    def stats(self, seq, targets, probes=None) -> dict:
        """Statistics at every position of ``seq`` (a list of token ids);
        ``targets[t]`` and ``probes[t]`` are token ids for position t."""
        S, n = self.max_len, len(seq)
        pad = lambda a: np.pad(np.asarray(a, np.int32), (0, S - len(a)))
        probes = targets if probes is None else probes
        with jax.default_matmul_precision("highest"):
            out = self._fn(self.w, *jax.device_put(
                (pad(seq), pad(targets), pad(probes)), self.device))
        return {k: np.asarray(v)[:n] for k, v in out.items()}
