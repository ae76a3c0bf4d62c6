"""FAC2 (factoring, x = 2) as arXiv:2101.07050 defines it, in integers.

Batches of P equal chunks; each batch takes half of what is left.  DCA
computes a chunk from its step alone, K_i = ceil(N / (P * 2**(i // P + 1)))
(the paper's closed form); CCA from the remaining work at the start of the
batch, K = ceil(R / (2 P)) (the recursion).  Both clamp the last chunk to
what remains.
"""


def sizes(n: int, p: int, mode: str) -> list[int]:
    out, left, step, k = [], n, 0, 0
    while left > 0:
        if step % p == 0:
            if mode == "dca":
                k = -(-n // (p << (step // p + 1)))
            else:
                k = -(-left // (2 * p))
            k = max(k, 1)
        out.append(min(k, left))
        left -= out[-1]
        step += 1
    return out
