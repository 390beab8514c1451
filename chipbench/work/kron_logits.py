"""Work of one serving-head call: full logits ``z (B, t1·t2)`` of ``B``
rows ``h (B, q1·q2)`` through the Kronecker head ``Σ_k A_k ⊗ B_k``.

FLOPs: the factor chain, ``2·B·r·(q1·q2·t1 + t1·q2·t2)`` (a multiply-add
counts 2). Bytes: read h (f32) and the factors (f32), write the f32 logits
over every vocabulary column, ``4·B·t1·t2``.
"""


def fwd(B, r, q, t, h_bytes=4, factor_bytes=4, out_bytes=4):
    (q1, q2), (t1, t2) = q, t
    flops = 2 * B * r * (q1 * q2 * t1 + t1 * q2 * t2)
    factors = r * (q1 * t1 + q2 * t2) * factor_bytes
    return flops, B * q1 * q2 * h_bytes + factors + B * t1 * t2 * out_bytes
