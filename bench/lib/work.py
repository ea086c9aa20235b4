"""Operations and bytes the algorithm needs, computed from shapes.

These counts are the yardstick of ``gemm_roofline``, ``attn_roofline`` and
``step_mfu``. They count no tile padding and no work that a row did not
need, so they read the same whatever kernel implements the operation: a
later kernel can neither inflate nor hide its own share.

Shapes come from the configuration file's ``model`` block (the model as
run). A tick's executable is one of three kinds (see ``TickWork``):

* ``decode``: every slot computes one position (``M = slots``);
* ``mixed``: every slot computes the padded chunk width
  (``M = slots * padded``), decode rows at 1 real token, the filling row at
  its chunk's ``take``;
* ``chunk``: one row computes the padded chunk alone (``M = padded``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

ACT_BYTES = 2      # bf16 activations in and out of every projection
KV_BYTES = 2       # bf16 keys and values in the page pools
SCALE_BYTES = 1    # one E8M0 exponent per block of weights


@dataclasses.dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act: str
    block_size: int = 32

    @classmethod
    def from_model(cls, model: dict, block_size: int = 32) -> "Shapes":
        return cls(n_layers=model["n_layers"], d_model=model["d_model"],
                   n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
                   head_dim=model["head_dim"], d_ff=model["d_ff"],
                   vocab=model["vocab"], act=model["act"],
                   block_size=block_size)

    def projections(self) -> List[Tuple[int, int]]:
        """(K, N) of every quantized projection of one layer."""
        d, q, kv, f = (self.d_model, self.n_heads * self.head_dim,
                       self.n_kv_heads * self.head_dim, self.d_ff)
        mlp = [(d, f), (d, f), (f, d)] if self.act == "swiglu" \
            else [(d, f), (f, d)]
        return [(d, q), (d, kv), (d, kv), (q, d)] + mlp

    @property
    def matmul_params(self) -> int:
        """Quantized weights of the whole stack."""
        return self.n_layers * sum(k * n for k, n in self.projections())

    @property
    def kv_bytes_per_token(self) -> int:
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * KV_BYTES


def gemm_work(m: int, k: int, n: int, bits: int,
              block_size: int) -> Tuple[float, float]:
    """(operations, bytes) of ``y (M,N) = x (M,K) @ dequant(W (K,N))``:
    the codes at ``bits`` a weight, one scale byte a block of K, bf16
    activations in and out."""
    flops = 2.0 * m * k * n
    nbytes = (k * n * bits / 8 + (k // block_size) * n * SCALE_BYTES
              + m * k * ACT_BYTES + m * n * ACT_BYTES)
    return flops, nbytes


def attn_work(s: Shapes, q_offset: int, q_len: int,
              page_size: int) -> Tuple[float, float]:
    """(operations, bytes) of one row's causal attention in ONE layer:
    ``q_len`` queries at positions ``q_offset ..`` over the keys before and
    at each; the row reads every page that holds one of its keys."""
    keys = q_len * q_offset + q_len * (q_len + 1) // 2
    flops = 4.0 * s.n_heads * s.head_dim * keys
    pages = math.ceil((q_offset + q_len) / page_size)
    nbytes = (pages * page_size * 2 * s.n_kv_heads * s.head_dim * KV_BYTES
              + 2 * q_len * s.n_heads * s.head_dim * ACT_BYTES)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The roofline: the larger of compute time and memory time."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")


@dataclasses.dataclass
class TickWork:
    """What one scheduler tick's executable computed.

    ``rows``: (q_offset, q_len, consumes_logits) of every LIVE row; ``m``:
    positions each projection computed; ``kind``: decode | mixed | chunk.
    """
    kind: str
    m: int
    rows: List[Tuple[int, int, bool]]


def tick_totals(s: Shapes, ticks: List[TickWork], bits: int,
                page_size: int, peak_flops: float = math.inf,
                peak_bw: float = math.inf) -> Dict[str, float]:
    """Least times and useful operations summed over ``ticks``.

    ``gemm_least_s``: every dequant-GEMM call at its computed M;
    ``attn_least_s``: paged-attention kernel calls (decode and mixed ticks;
    a chunk that runs alone attends through XLA, not those kernels);
    ``model_flops``: real tokens only, as the model needs them: projections,
    causal attention over each row's real context, and the lm_head on each
    row whose logits are consumed. Without peaks the least times read 0.
    """
    out = {"gemm_least_s": 0.0, "gemm_flops": 0.0, "gemm_bytes": 0.0,
           "gemm_compute_bound_s": 0.0, "attn_least_s": 0.0,
           "attn_compute_bound_s": 0.0, "model_flops": 0.0,
           "real_tokens": 0.0, "positions": 0.0}
    proj = s.projections()
    per_token = 2.0 * s.matmul_params
    head = 2.0 * s.d_model * s.vocab
    for t in ticks:
        for k, n in proj:
            f, b = gemm_work(t.m, k, n, bits, s.block_size)
            lt, bound = least_time(f, b, peak_flops, peak_bw)
            out["gemm_least_s"] += lt * s.n_layers
            out["gemm_flops"] += f * s.n_layers
            out["gemm_bytes"] += b * s.n_layers
            if bound == "compute":
                out["gemm_compute_bound_s"] += lt * s.n_layers
        out["positions"] += t.m
        af = ab = 0.0
        for off, q, consumes in t.rows:
            f, b = attn_work(s, off, q, page_size)
            af, ab = af + f, ab + b
            out["model_flops"] += per_token * q + f * s.n_layers \
                + (head if consumes else 0.0)
            out["real_tokens"] += q
        if t.kind != "chunk" and t.rows:
            # one kernel call a layer covers every live row of the tick
            lt, bound = least_time(af, ab, peak_flops, peak_bw)
            out["attn_least_s"] += lt * s.n_layers
            if bound == "compute":
                out["attn_compute_bound_s"] += lt * s.n_layers
    return out
