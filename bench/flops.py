"""Operations and bytes that each algorithm on the serving path needs,
computed from the configuration's shapes alone: the parts that model
families (``bench/models/<family>.py``) build their ``work`` count from.

These are the least work of the algorithm, not of any implementation:
a step that reads more (a dense cache at its full length, an f32
upcast, a second copy of a tied head) is slower than this, and its
roofline share says by how much.  ``Dims``, ``decode`` and ``prefill``
count the dense block with grouped-query attention, from the
configuration file's published keys (``hidden_size`` and so on);
weights are served in bf16 (2 bytes an element).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Tuple

WEIGHT_BYTES = 2        # bf16, the precision the configurations are served in


class Count(NamedTuple):
    """A family's least work over one traced stretch, as its ``work``
    returns it: (FLOPs, bytes) of the decode steps and of the prefills."""
    decode: Tuple[int, int]
    prefill: Tuple[int, int]


@dataclass(frozen=True)
class Dims:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    qkv_bias: bool

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one layer: the q, k, v
        and o projections and the gated MLP's gate, up and down."""
        q = self.hidden * self.heads * self.head_dim
        kv = 2 * self.hidden * self.kv_heads * self.head_dim
        o = self.heads * self.head_dim * self.hidden
        mlp = 3 * self.hidden * self.ffn
        return q + kv + o + mlp

    @property
    def layer_vector_params(self) -> int:
        """Two norm scales, and the q/k/v biases where the model has them."""
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim
        return 2 * self.hidden + (bias if self.qkv_bias else 0)

    @property
    def head_params(self) -> int:
        """The output projection (the tied embedding read as a matrix)."""
        return self.hidden * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Every weight a forward step must read once: all layers, the
        final norm and the head.  The embedding lookup reads only the rows
        of the step's tokens, which is negligible beside the head."""
        per_layer = self.layer_matmul_params + self.layer_vector_params
        return WEIGHT_BYTES * (self.layers * per_layer + self.hidden
                               + self.head_params)

    @property
    def kv_bytes_per_position(self) -> int:
        """K and V of one position over all layers, in bf16."""
        return WEIGHT_BYTES * 2 * self.layers * self.kv_heads * self.head_dim

    @property
    def attn_flops_per_pair(self) -> int:
        """One query position against one key position over all layers:
        q.k and p.v are each 2 * head_dim multiply-adds per head."""
        return 4 * self.layers * self.heads * self.head_dim


def dims(cfg: dict) -> Dims:
    """Shapes from a configuration file's published keys."""
    heads = cfg["num_attention_heads"]
    return Dims(layers=cfg["num_hidden_layers"], hidden=cfg["hidden_size"],
                heads=heads, kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
                ffn=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                qkv_bias=bool(cfg.get("qkv_bias", False)))


def decode(d: Dims, steps: int, tokens: int, context_sum: int):
    """(FLOPs, bytes) of ``steps`` decode steps that together produced
    ``tokens`` tokens, where ``context_sum`` is the sum over those tokens
    of the positions each one's query attends to (its prompt, the tokens
    before it and itself).

    - FLOPs: every token multiplies through every layer's matrices and
      the head (2 per multiply-add), and attends over its own context.
    - Bytes: each step reads all weights once, however many slots it
      serves; each token reads K and V at its own context length, not at
      the cache's capacity, and writes one position of K and V.
    """
    flops = (2 * tokens * (d.layers * d.layer_matmul_params + d.head_params)
             + d.attn_flops_per_pair * context_sum)
    nbytes = (steps * d.weight_bytes
              + d.kv_bytes_per_position * (context_sum + tokens))
    return flops, nbytes


def prefill(d: Dims, prompt_lens, calls: int):
    """(FLOPs, bytes) of forwarding prompts of ``prompt_lens`` positions
    in ``calls`` batched calls.

    - FLOPs: every prompt position multiplies through every layer; only
      the last position needs the head (it yields the first token); the
      causal attention of a P-position prompt covers P(P+1)/2 pairs.
    - Bytes: each call reads all weights once; each position's K and V
      are written once.
    """
    positions = sum(prompt_lens)
    pairs = sum(p * (p + 1) // 2 for p in prompt_lens)
    flops = (2 * positions * d.layers * d.layer_matmul_params
             + 2 * len(prompt_lens) * d.head_params
             + d.attn_flops_per_pair * pairs)
    nbytes = calls * d.weight_bytes + d.kv_bytes_per_position * positions
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bw: float) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak_flops, nbytes / peak_bw)
