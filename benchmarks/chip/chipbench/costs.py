"""Operations and bytes of the served work, from shapes alone.

``Step`` records what one engine step carried, as the harness saw it from
outside the program: the rows of the decode lane with their context
lengths, and the prefill chunk (its start, true and padded length).

The fused W4A8 GEMM (``kernels/w4a8_fused.py``) is called once per
quantized linear per layer, on every row of the step's token batch: ``M``
is the slots plus the padded chunk in a mixed step, the slots in a decode
step. Per call it needs ``2 M N K`` operations for the product and
``2 M r (N + K)`` for the LoRC side path, and reads the packed codes
(``N K / 2`` bytes), the int8 shifts (``N K / g``), the f32 row maxima
(``4 N``), the bf16 LoRC factors (``2 r (N + K)``) and the bf16 activations
(``2 M K``), and writes the bf16 output (``2 M N``).

The paged decode kernel (``kernels/decode_attn.py``) runs once per layer
over every slot row, each at its true length plus the appended token (an
idle row at length 1). The bytes it needs are those of the pages those
lengths cover: FP8 K and V codes (``page * n_kv * hd`` bytes each) with
their f32 page maximum and int32 per-head shifts; plus the bf16 queries and
outputs. Operations: ``4 H hd L`` per row and layer (scores and values).

``model_flops`` counts what the model needs for the step's true tokens
only (no pad rows, no idle slots): every quantized linear with LoRC,
causal attention over the true lengths, and the tied head for each row
whose logits are used.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple


@dataclasses.dataclass(frozen=True)
class Step:
    slots: int
    decode_lens: Tuple[int, ...]  # context length of each decoding row
    chunk_pos: int = 0
    chunk_take: int = 0  # true prefill tokens (0: a decode step)
    chunk_padded: int = 0


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def linears(arch: dict) -> List[Tuple[int, int]]:
    """(N, K) of each quantized linear of one layer."""
    d, h, kv = arch["d_model"], arch["n_heads"], arch["n_kv_heads"]
    hd = d // h
    ff = arch["d_ff"]
    out = [(h * hd, d), (kv * hd, d), (kv * hd, d), (d, h * hd), (ff, d),
           (d, ff)]
    if arch.get("mlp_gated", True):
        out.append((ff, d))
    return out


def gemm_cost(m: int, n: int, k: int, group: int, rank: int):
    flops = 2 * m * n * k + 2 * m * rank * (n + k)
    nbytes = (n * k // 2 + n * (k // group) + 4 * n + 2 * rank * (n + k)
              + 2 * m * k + 2 * m * n)
    return flops, nbytes


def step_gemm_least_s(step: Step, arch: dict, quant: dict,
                      peaks: dict) -> float:
    """The least time the step's fused-GEMM calls could take: for each
    call, the larger of its operations at the bf16 peak and its bytes at
    the HBM bandwidth. Every row of the call counts: ``M`` is the slots
    plus the padded chunk."""
    m = step.slots + step.chunk_padded
    least = 0.0
    for n, k in linears(arch):
        f, b = gemm_cost(m, n, k, quant["group_size"], quant["lorc_rank"])
        least += max(f / peaks["flops"], b / peaks["hbm_bytes_per_s"])
    return least * arch["n_layers"]


def step_decode_attn(step: Step, arch: dict, page: int):
    """Operations and bytes of the paged decode kernel in one step."""
    d, h, kv = arch["d_model"], arch["n_heads"], arch["n_kv_heads"]
    hd = d // h
    lens = list(step.decode_lens) + [0] * (step.slots - len(step.decode_lens))
    page_bytes = 2 * (page * kv * hd + 4 + 4 * kv)
    pages = sum(-(-(n + 1) // page) for n in lens)
    nbytes = pages * page_bytes + step.slots * h * hd * 2 * 2
    flops = sum(4 * h * hd * (n + 1) for n in lens)
    return flops * arch["n_layers"], nbytes * arch["n_layers"]


def model_flops(step: Step, arch: dict, quant: dict) -> int:
    d, h = arch["d_model"], arch["n_heads"]
    hd = d // h
    r = quant["lorc_rank"]
    per_tok = sum(2 * n * k + 2 * r * (n + k) for n, k in linears(arch))
    toks = len(step.decode_lens) + step.chunk_take
    attn = sum(4 * h * hd * (n + 1) for n in step.decode_lens)
    p, t = step.chunk_pos, step.chunk_take
    attn += 4 * h * hd * (t * p + t * (t + 1) // 2)
    layers = arch["n_layers"] * (per_tok * toks + attn)
    heads = len(step.decode_lens) + (1 if t else 0)
    return layers + 2 * d * arch["vocab_size"] * heads
