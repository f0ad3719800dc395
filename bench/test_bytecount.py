"""The HBM byte counts against hand counts."""
import pytest

from bench import bytecount


@pytest.mark.parametrize("n, e, iters, want", [
    # 4N for the labels, then per iteration 12E (triples) + 8N (pids in,
    # pids out)
    (10, 0, 0, 40),
    (10, 20, 1, 40 + 240 + 80),
    (2_300_000, 6_100_000, 5, 9_200_000 + 5 * (73_200_000 + 18_400_000)),
])
def test_build_bytes(n, e, iters, want):
    assert bytecount.build_bytes(n, e, iters) == want


@pytest.mark.parametrize("eq, b, nt, ns, want", [
    (0, 16, 0, 0, 0),
    (1, 1, 1, 1, 12 + 1 + 1),
    # 6M quotient edges, a wave of 16 over 2M target and 2M source blocks
    (6_000_000, 16, 2_000_000, 2_100_000,
     72_000_000 + 32_000_000 + 33_600_000),
])
def test_hop_bytes(eq, b, nt, ns, want):
    assert bytecount.hop_bytes(eq, b, nt, ns) == want
