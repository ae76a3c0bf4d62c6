"""STATIC: P chunks of N / P, the first N mod P of them one larger."""


def sizes(n: int, p: int, mode: str) -> list[int]:
    return [n // p + (i < n % p) for i in range(p)]
