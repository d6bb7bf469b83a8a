"""Operations and bytes that the algorithm of a dense GQA decoder needs,
from its published sizes and the real lengths of the work.

This is the benchmark's own arithmetic, adapted from the repository's
``configs/analysis.model_flops`` (6·N·T plus attention) and fed actual
lengths: a decode step counts each live slot at its own context, not the
cache's full length.  It counts what the algorithm needs, never what one
implementation happens to touch, so a roofline share reads the same work
whatever implements the kernel.  A multiply-add is two operations.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


@dataclass(frozen=True)
class Arch:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> Arch:
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one block: q, k, v and
        o projections and the three SwiGLU matrices."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return attn + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab


def attention_flops(a: Arch, context: int) -> int:
    """Scores and weighted values of one query token over ``context`` keys,
    all layers."""
    return 4 * a.layers * a.heads * a.head_dim * context


def decode_token_flops(a: Arch, kv_len: int) -> int:
    """Forward of one decoded token whose cache holds ``kv_len`` rows
    (itself included), head included."""
    return (2 * (a.layers * a.layer_matmul_params + a.head_params)
            + attention_flops(a, kv_len))


def train_step_flops(a: Arch, batch: int, seq: int) -> int:
    """Forward and backward (3x the forward) of one causal-LM step on
    ``batch`` rows of ``seq`` tokens; the head scores ``seq - 1`` positions.
    Recomputation is not counted."""
    body = seq * 2 * a.layers * a.layer_matmul_params
    head = (seq - 1) * 2 * a.head_params
    attn = 4 * a.layers * a.heads * a.head_dim * seq * (seq + 1) // 2
    return 3 * batch * (body + head + attn)


def decode_attention_work(a: Arch, kv_len: int) -> tuple[int, int]:
    """(operations, bytes) of one layer's decode attention for one slot:
    read ``kv_len`` rows of K and V, read q, write o."""
    flops = 4 * a.heads * a.head_dim * kv_len
    kv = 2 * kv_len * a.kv_heads * a.head_dim * BF16
    qo = 2 * a.heads * a.head_dim * BF16
    return flops, kv + qo


def flash_attention_work(a: Arch, batch: int, seq: int) -> tuple[int, int]:
    """(operations, bytes) of one layer's causal attention forward over
    ``batch`` rows of ``seq`` tokens: read q, k, v, write o."""
    flops = 4 * batch * a.heads * a.head_dim * seq * (seq + 1) // 2
    qo = 2 * batch * seq * a.heads * a.head_dim * BF16
    kv = 2 * batch * seq * a.kv_heads * a.head_dim * BF16
    return flops, qo + kv


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """Share (%) of the roofline that ``seconds`` of device time reached,
    and which bound applies: the least time is the larger of operations
    over peak operations and bytes over peak bandwidth."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
