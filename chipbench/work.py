"""Operations and bytes the algorithm needs, counted from a configuration's
sizes, not from the compiled program: the same work whatever implements it.

Each family counts its own model in ``families/<family>.py``
(``model_flops_per_item``, ``dense_sites`` and its kernels' work); this
module holds the arithmetic they share:

* Model FLOPs: forward and backward (3 x the forward) of every matmul or
  convolution, plus causal attention; no recompute, no K-FAC work, no
  embedding gather.
* Kernel work: per call of ``block_precond_left``/``right`` (a blocked
  inverse times the gradient, f32), ``swa_attention_fwd_res`` /
  ``swa_attention_bwd`` (causal flash attention; the backward counted as
  twice the forward, without recomputing the scores) and ``factor_sum``
  (the blocked symmetric rank-n update, n b (b + 1) per block).
  Bytes are what each call must read and write once.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def blocks(d: int, max_dim: int) -> tuple[int, int]:
    nb = max(1, -(-d // max_dim))
    return nb, -(-d // nb)


def syrk(n: int, d: int, max_dim: int) -> Work:
    """``factor_sum`` of ``n`` f32 rows of ``d`` features into diagonal
    blocks of at most ``max_dim``."""
    nb, b = blocks(d, max_dim)
    return Work(n * nb * b * (b + 1), 4 * (n * d + nb * b * b))


def precond_work(sites, max_dim: int) -> Work:
    """``block_precond_left`` and ``_right`` of one step over every site
    with a blocked factor: f32 inverse blocks, gradient and result. A site
    is ``(name, count, d_in, d_out, A blocked, G blocked)``."""
    w = Work()
    for _, count, d_in, d_out, a_full, g_full in sites:
        if a_full:
            nb, b = blocks(d_in, max_dim)
            w = w + Work(2 * nb * b * b * d_out,
                         4 * (nb * b * b + 2 * nb * b * d_out)) * count
        if g_full:
            nb, b = blocks(d_out, max_dim)
            w = w + Work(2 * d_in * nb * b * b,
                         4 * (nb * b * b + 2 * d_in * nb * b)) * count
    return w


def roofline_share(work: Work, seconds: float, peaks: dict) -> float:
    """The least time the chip could take, over the time it took, in %."""
    least = max(work.flops / peaks["bf16_flops_per_s"],
                work.bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
