"""Work of one ``kron_matmul`` call: ``x (B, q1·q2) @ Σ_k A_k ⊗ B_k`` with
``A (r, q1, t1)``, ``B (r, q2, t2)``, as the order-2 factor chain computes it.

FLOPs (a multiply-add counts 2):

* forward: ``Y[b,k,i,c] = Σ_a X[b,a,c] A[k,a,i]`` then
  ``Z[b,i,j] = Σ_{k,c} Y[b,k,i,c] B[k,c,j]``:
  ``2·B·r·(q1·q2·t1 + t1·q2·t2)``;
* backward: the same two contractions for dX (through dY) and one each for
  dA and dB: twice the forward, with ``Y`` counted as kept (a kernel that
  recomputes it does more work than counted here).

Bytes (HBM, each operand read once and each result written once): forward
reads x and the factors and writes z; backward reads x, the output's
gradient and the factors and writes dx and the factor gradients.
"""


def fwd(B, r, q, t, x_bytes=2, out_bytes=2, factor_bytes=4):
    (q1, q2), (t1, t2) = q, t
    flops = 2 * B * r * (q1 * q2 * t1 + t1 * q2 * t2)
    factors = r * (q1 * t1 + q2 * t2) * factor_bytes
    return flops, B * q1 * q2 * x_bytes + factors + B * t1 * t2 * out_bytes


def bwd(B, r, q, t, x_bytes=2, g_bytes=2, factor_bytes=4):
    (q1, q2), (t1, t2) = q, t
    flops = 2 * fwd(B, r, q, t)[0]
    factors = r * (q1 * t1 + q2 * t2) * factor_bytes
    io = B * q1 * q2 * x_bytes + B * t1 * t2 * g_bytes + B * q1 * q2 * x_bytes
    return flops, io + 2 * factors
