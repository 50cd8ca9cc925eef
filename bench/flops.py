"""Operations and bytes the algorithm needs, from shapes alone.

These are the numerators of every utilization and roofline share the
benchmark reports. They count what the model requires, not what one
implementation executes: no recomputation, no padded positions, no
dead decode slots, no reads of cache rows beyond a sequence's live
prefix. ``conf`` is a configuration file (``bench/configs/*.json``).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

BF16 = 2
F32 = 4


def layer_matmul_params(conf: Dict[str, Any]) -> int:
    d, H, KV, hd, ff = (conf["d_model"], conf["num_heads"],
                        conf["num_kv_heads"], conf["head_dim"], conf["d_ff"])
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def layers(conf: Dict[str, Any]) -> int:
    return conf["base_layers"] + conf["mod_layers"]


def attn_flops(conf: Dict[str, Any], rows: float, n_layers: int) -> float:
    """Scores and values of one query over ``rows`` keys, per layer."""
    return 4.0 * rows * conf["num_heads"] * conf["head_dim"] * n_layers


def base_fwd_flops(conf, rows: float) -> float:
    """Base block forward of one position attending ``rows`` keys."""
    n = conf["base_layers"]
    return (2.0 * (n * layer_matmul_params(conf)
                   + conf["d_model"] * conf["d_fusion"])
            + attn_flops(conf, rows, n))


def mod_fwd_flops(conf, rows: float, head: bool = True) -> float:
    """Modular block forward of one position; ``head`` adds the LM head."""
    n = conf["mod_layers"]
    f = (2.0 * (n * layer_matmul_params(conf)
                + conf["d_fusion"] * conf["d_model"])
         + attn_flops(conf, rows, n))
    if head:
        f += 2.0 * conf["d_model"] * conf["vocab_size"]
    return f


# ------------------------------------------------------------- serving


def decode_flash_bytes(conf, rows: int) -> float:
    """One decode position's attention, all layers: the K/V of its live
    prefix (``rows`` cache rows, bf16) plus its query and output."""
    H, KV, hd = conf["num_heads"], conf["num_kv_heads"], conf["head_dim"]
    per_layer = rows * KV * hd * 2 * BF16 + 2 * H * hd * BF16
    return float(per_layer * layers(conf))


def serve_positions(work: Iterable[Tuple[int, bool]], conf
                    ) -> Dict[str, float]:
    """Totals over computed positions, each (keys attended, emits a
    token). A prefill position that emits no token skips the LM head."""
    flops = attn_bytes = attn_fl = 0.0
    n = 0
    for rows, head in work:
        flops += base_fwd_flops(conf, rows) + mod_fwd_flops(conf, rows, head)
        attn_bytes += decode_flash_bytes(conf, rows)
        attn_fl += attn_flops(conf, rows, layers(conf))
        n += 1
    return {"model_flops": flops, "flash_bytes": attn_bytes,
            "flash_flops": attn_fl, "positions": n}


# ------------------------------------------------------------ training


def round_flops(conf, job: Dict[str, Any]) -> float:
    """Model FLOPs one IFL round requires over all clients.

    Phase 1, tau steps per client: base forward and backward (3x the
    forward) plus the modular forward and its backward to the
    activations only (2x). Phase 2: one base forward on the fusion
    minibatch. Phase 3: each client's modular forward and backward
    (3x) on every client's chunk. Attention is causal: a position
    attends (S + 1) / 2 keys on average."""
    N, tau, B, S = job["clients"], job["tau"], job["batch"], job["seq"]
    rows = (S + 1) / 2.0
    fb, fm = base_fwd_flops(conf, rows), mod_fwd_flops(conf, rows)
    tokens = B * S
    return N * tokens * (tau * (3 * fb + 2 * fm) + fb + N * 3 * fm)


def round_tokens(job: Dict[str, Any]) -> int:
    return job["clients"] * (job["tau"] + 1) * job["batch"] * job["seq"]


def flash_attention_call(dims) -> Tuple[float, float]:
    """(FLOPs, bytes) of one forward ``flash_attention`` call whose
    output is ``dims`` = (..., S, hd): every leading dimension (clients,
    batch, heads) is one causal attention over S; q, k, v are read and
    the output written in bf16."""
    *lead, S, hd = dims
    bh = 1
    for x in lead:
        bh *= x
    flops = bh * 4.0 * hd * S * (S + 1) / 2.0
    nbytes = bh * 4.0 * S * hd * BF16
    return flops, nbytes


def wire_encode_ef_call(rows: int, d: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one fused EF + int4 encode of ``rows`` fusion
    rows of width d: read z (bf16) and the residual (f32); write the
    packed nibbles, one f32 scale per row and the new residual (f32)."""
    nbytes = rows * (d * BF16 + d * F32 + d // 2 + F32 + d * F32)
    return rows * d * 8.0, float(nbytes)


def roofline_s(flops: float, nbytes: float, peaks: Dict[str, float]
               ) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])
