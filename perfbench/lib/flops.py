"""Operations and bytes the *algorithm* needs, from shapes alone. What an
implementation moves beyond that (a gathered copy, a recompute) is not
counted, so a share built on these cannot pass 100% by construction of the
count — only by a time that leaves out part of the work.

Conventions: one multiply-add = 2 FLOPs; causal attention counts the lower
triangle (half of S x S); the embedding gather is a lookup, not a matmul.
"""


def matmul_params(d: dict) -> int:
    """Parameters that take part in a matmul for every token: all but the
    embedding table and the norm scales."""
    nq = d["n_heads"] * d["head_dim"]
    nkv = d["n_kv_heads"] * d["head_dim"]
    per_layer = (d["dim"] * (nq + 2 * nkv) + nq * d["dim"]
                 + 3 * d["dim"] * d["hidden"])
    return d["n_layers"] * per_layer + d["dim"] * d["vocab"]


def attn_flops_fwd(d: dict, q_len: int, kv_len: int, causal: bool) -> float:
    """QK^T and PV for ``q_len`` queries over ``kv_len`` keys, all layers.
    Causal with q_len == kv_len counts the triangle."""
    full = 2.0 * 2.0 * d["n_heads"] * d["head_dim"] * q_len * kv_len
    if causal and q_len == kv_len:
        full *= (q_len + 1) / (2.0 * q_len)
    return d["n_layers"] * full


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward (3x forward), causal attention, no recompute."""
    fwd = 2.0 * matmul_params(d) + attn_flops_fwd(
        d, seq_len, seq_len, causal=True) / seq_len
    return 3.0 * fwd


def flash_attn_flops(d: dict, batch: int, seq_len: int) -> float:
    """Causal attention forward + backward of one step: the backward makes
    two matmuls for each of the forward's (dQ, dK, dV and dP), 2x forward;
    the kernel's own recompute of P is not counted."""
    fwd = attn_flops_fwd(d, seq_len, seq_len, causal=True) * batch
    return 3.0 * fwd


def flash_attn_bytes(d: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv. Once each."""
    q = batch * seq_len * d["n_heads"] * d["head_dim"] * itemsize
    kv = batch * seq_len * d["n_kv_heads"] * d["head_dim"] * itemsize
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return float(d["n_layers"] * (fwd + bwd))


def kv_bytes_per_token(d: dict, itemsize: int = 2) -> int:
    return d["n_layers"] * 2 * d["n_kv_heads"] * d["head_dim"] * itemsize


def paged_read_bytes(d: dict, live_tokens: int, itemsize: int = 2) -> float:
    """The live keys and values read once: what a decode step's attention
    needs whatever implements it."""
    return float(kv_bytes_per_token(d, itemsize) * live_tokens)


def serve_flops(d: dict, new_tokens: int, ctx_token_pairs: int) -> float:
    """Forward FLOPs of serving: 2 x matmul params for each token processed
    (prefill or decode) plus attention over its context;
    ``ctx_token_pairs`` is the sum over processed tokens of the context
    length each attended to."""
    attn = 2.0 * 2.0 * d["n_heads"] * d["head_dim"] * d["n_layers"]
    return 2.0 * matmul_params(d) * new_tokens + attn * ctx_token_pairs


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"],
               bytes_ / peaks["hbm_bytes_per_s"])
