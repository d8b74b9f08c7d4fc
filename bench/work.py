"""The work an algorithm needs, counted from shapes alone.

These counts are the numerators of every roofline share and utilization
the benchmark reports.  They count what the mathematics requires, never
what an implementation happens to do: no tile or chunk length enters, a
causal mask halves the attention, and each operand is read or written
once at its true size.  So removing wasted work from a kernel raises its
share, and no count can be inflated by doing more of it.

Configurations are the benchmark's own files (``bench/configs``), whose
keys follow each model's published ``config.json``.
"""

from __future__ import annotations

__all__ = ["Work", "flash_attention", "ssd_scan", "forward_step",
           "kernel_calls", "logit_width"]


def logit_width(c: dict) -> int:
    """Width of the model's output head: the vocabulary, padded up to
    ``pad_vocab_size_multiple`` where the published config states one."""
    m = c.get("pad_vocab_size_multiple", 1)
    return -(-c["vocab_size"] // m) * m


class Work:
    """Operations and bytes of one call; ``min_seconds`` is the roofline."""

    def __init__(self, flops: float, bytes_: float):
        self.flops = float(flops)
        self.bytes = float(bytes_)

    def min_seconds(self, peaks: dict) -> tuple[float, str]:
        """The least time the chip could take, and which rate bounds it."""
        t_flops = self.flops / peaks["bf16_flops_per_s"]
        t_bytes = self.bytes / peaks["hbm_bytes_per_s"]
        if t_flops >= t_bytes:
            return t_flops, "compute"
        return t_bytes, "memory"


def flash_attention(batch: int, q_heads: int, kv_heads: int, seq: int,
                    head_dim: int, causal: bool = True,
                    dtype_bytes: int = 2) -> Work:
    """Self-attention over ``seq`` positions: QK^T and PV on the pairs the
    mask keeps (2 FLOPs per multiply-add each, so 4 * head_dim a pair);
    q and o at the query heads, k and v at the KV heads, each moved once."""
    pairs = seq * (seq + 1) / 2 if causal else seq * seq
    flops = 4 * head_dim * pairs * batch * q_heads
    moved = dtype_bytes * batch * seq * head_dim * 2 * (q_heads + kv_heads)
    return Work(flops, moved)


def ssd_scan(batch: int, heads: int, seq: int, head_dim: int, state: int,
             x_bytes: int = 2, bc_bytes: int = 2, dt_bytes: int = 4) -> Work:
    """The Mamba-2 recurrence h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t^T,
    y_t = C_t h_t: decay, outer product and add (3 n dh) plus the readout
    (2 n dh), per token and head.  x and y at (batch, seq, heads*head_dim),
    B and C once at their shared (batch, seq, state) size, dt at (batch,
    seq, heads)."""
    flops = 5 * state * head_dim * batch * seq * heads
    moved = batch * seq * (2 * heads * head_dim * x_bytes
                           + 2 * state * bc_bytes + heads * dt_bytes)
    return Work(flops, moved)


def _dense_layer_matmul_params(c: dict) -> int:
    d, dh = c["hidden_size"], c["head_dim"]
    q = c["num_attention_heads"] * dh
    kv = c["num_key_value_heads"] * dh
    return d * (q + 2 * kv) + q * d + 3 * d * c["intermediate_size"]


def _mamba2_dims(c: dict) -> tuple[int, int, int, int]:
    d_inner = c["expand"] * c["d_model"]
    heads = d_inner // c["headdim"]
    return d_inner, heads, c["headdim"], c["d_state"]


def kernel_calls(c: dict, batch: int, seq: int) -> dict[str, Work]:
    """The tuned kernel's needed work per call (one call per layer) in a
    full-sequence forward of (batch, seq) tokens."""
    if c["family"] == "dense_lm":
        return {"flash_attention": flash_attention(
            batch, c["num_attention_heads"], c["num_key_value_heads"], seq,
            c["head_dim"])}
    if c["family"] == "mamba2":
        _, heads, dh, n = _mamba2_dims(c)
        return {"ssd_scan": ssd_scan(batch, heads, seq, dh, n)}
    raise ValueError(f"unknown family {c['family']!r}")


def forward_step(c: dict, batch: int, seq: int, logit_positions: int
                 ) -> float:
    """FLOPs one forward step needs: 2 per non-embedding matmul parameter
    per token, the attention or recurrence, and the unembedding of
    ``logit_positions`` positions per row (over the output head's width)."""
    tokens = batch * seq
    if c["family"] == "dense_lm":
        layers = c["num_hidden_layers"]
        d = c["hidden_size"]
        matmul = _dense_layer_matmul_params(c) * layers
    elif c["family"] == "mamba2":
        layers = c["n_layer"]
        d = c["d_model"]
        d_inner, heads, _, n = _mamba2_dims(c)
        matmul = layers * (d * (2 * d_inner + 2 * n + heads) + d_inner * d)
    else:
        raise ValueError(f"unknown family {c['family']!r}")
    mixer = sum(w.flops for w in kernel_calls(c, batch, seq).values())
    unembed = 2 * d * logit_width(c) * batch * logit_positions
    return 2 * matmul * tokens + mixer * layers + unembed
