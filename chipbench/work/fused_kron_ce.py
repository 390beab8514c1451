"""Work of one ``fused_kron_ce`` call: the per-row cross-entropy of ``B``
rows ``h (B, q1·q2)`` against the Kronecker head ``Σ_k A_k ⊗ B_k``
(``A (r, q1, t1)``, ``B (r, q2, t2)``), the logits never stored.

FLOPs (a multiply-add counts 2):

* forward: the head's factor chain over every vocabulary column,
  ``2·B·r·(q1·q2·t1 + t1·q2·t2)``, plus the online log-sum-exp,
  ``4·B·t1·t2`` (max, subtract, exp, add);
* backward: the softmax's gradient, ``3·B·t1·t2``, and the chain's
  backward, twice the forward chain; the logits the backward needs again
  are not counted a second time (a kernel that recomputes them does more
  work than counted here).

Bytes (HBM): the forward reads h (f32), the labels and the factors and
writes three f32 statistics per row; the backward reads h, the labels, the
statistics, the row gradients and the factors, and writes dh and the factor
gradients. The (B, vocab) logits are never in HBM.
"""


def _chain(B, r, q, t):
    (q1, q2), (t1, t2) = q, t
    return 2 * B * r * (q1 * q2 * t1 + t1 * q2 * t2)


def fwd(B, r, q, t, h_bytes=4, factor_bytes=4):
    (q1, q2), (t1, t2) = q, t
    flops = _chain(B, r, q, t) + 4 * B * t1 * t2
    factors = r * (q1 * t1 + q2 * t2) * factor_bytes
    return flops, B * q1 * q2 * h_bytes + 4 * B + factors + 3 * 4 * B


def bwd(B, r, q, t, h_bytes=4, factor_bytes=4):
    (q1, q2), (t1, t2) = q, t
    flops = 2 * _chain(B, r, q, t) + 3 * B * t1 * t2
    factors = r * (q1 * t1 + q2 * t2) * factor_bytes
    rows = B * q1 * q2 * h_bytes
    return flops, 2 * rows + 4 * B * 5 + 2 * factors
