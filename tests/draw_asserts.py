"""Boundary-aware activation parity assertion (DESIGN.md §9).

The implicit-im2col kernel gathers its patch rows in VMEM, so its packed
matmul is not *operand-identical* to the oracle's dot over a materialized
patch matrix — u can differ by an ulp. Given the same folded probability q
the Bernoulli draw is bit-exact (``mtj.bernoulli_from_bits`` is shared),
so the only legitimate end-to-end mismatch is a q that an ulp-level u
difference pushed across a uint16 draw-word boundary. This helper asserts
exactly that (the rule itself is ``kernels.ref.draw_mismatches``, shared
with ``chip_smoke.py``): mismatches must be RARE and must all sit within
one word of the threshold — anything else is a real kernel bug.
"""
from repro.kernels import ref


def assert_draws_match_modulo_word_boundary(acts, q_ref, bits,
                                            max_flips: int = 8):
    """acts (N, C) float {0,1} from the kernel pipeline; q_ref (N, C) the
    ORACLE's folded activation probability (``ref.p2m_conv_ref_q``);
    bits the (N, C) draw words both sides consumed."""
    n_flips, off_boundary = ref.draw_mismatches(acts, q_ref, bits)
    assert n_flips <= max_flips, (
        f"{n_flips} draw mismatches (> {max_flips}): more than "
        "quantization-boundary noise — kernel vs oracle diverged")
    assert not off_boundary, (
        "draw mismatch away from the uint16 word boundary — not an "
        "ulp-of-u effect; kernel vs oracle diverged")
