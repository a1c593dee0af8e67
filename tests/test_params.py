"""Golden tests for the SNR-conditioned transition model (parity with
reference ContextParameterProvider.cpp:69-113 semantics)."""

import jax.numpy as jnp
import numpy as np
import pytest

from pbccs_tpu.models.arrow.params import (
    CONTEXT_COEFF,
    TRANS_BRANCH,
    TRANS_DARK,
    TRANS_MATCH,
    TRANS_STICK,
    context_index,
    decode_bases,
    encode_bases,
    revcomp,
    snr_to_transition_table,
    template_transition_params,
)
from pbccs_tpu.simulate import make_transition_track


def golden_transition(ctx: int, snr: float):
    """Literal transcription of the reference formula for one context."""
    powers = np.array([1.0, snr, snr**2, snr**3])
    xb = np.exp(CONTEXT_COEFF[ctx] @ powers)  # [dark, match, stick]
    s = 1.0 + xb.sum()
    return xb[1] / s, 1.0 / s, xb[2] / s, xb[0] / s  # match, branch, stick, dark


def test_table_matches_golden():
    snr = np.array([7.0, 8.5, 6.2, 11.0])
    table = np.asarray(snr_to_transition_table(jnp.asarray(snr)))
    for ctx in range(8):
        chan = ctx % 4
        m, b, s, d = golden_transition(ctx, snr[chan])
        np.testing.assert_allclose(table[ctx], [m, b, s, d], rtol=1e-4)
        assert abs(table[ctx].sum() - 1.0) < 1e-5


def test_context_index():
    # AA context: cur==next==A -> 0 ; NA: cur!=A, next=A -> 4
    assert int(context_index(jnp.int32(0), jnp.int32(0))) == 0
    assert int(context_index(jnp.int32(3), jnp.int32(3))) == 3
    assert int(context_index(jnp.int32(1), jnp.int32(0))) == 4
    assert int(context_index(jnp.int32(0), jnp.int32(3))) == 7


def test_template_track_matches_numpy_mirror():
    rng = np.random.default_rng(0)
    tpl = rng.integers(0, 4, 40).astype(np.int8)
    snr = np.array([8.0, 9.0, 7.5, 10.0])
    track_np = make_transition_track(tpl, snr)
    table = snr_to_transition_table(jnp.asarray(snr))
    track_jax = np.asarray(template_transition_params(jnp.asarray(tpl), table))
    np.testing.assert_allclose(track_jax, track_np, rtol=1e-4, atol=1e-6)
    # final position is the zero sentinel
    assert np.all(track_jax[-1] == 0)


def test_encode_decode_revcomp():
    s = "ACGTTGCA"
    e = encode_bases(s)
    assert decode_bases(e) == s
    assert decode_bases(revcomp(e)) == "TGCAACGT"


@pytest.mark.parametrize("codes", [
    np.array([0, 1, 2, 3, 4, 4, 3, 2], dtype=np.int8),   # pads dropped
    np.array([-1, 0, 5, 127, 3], dtype=np.int8),         # anything outside 0..3
    np.array([3, 3, 0, 4], dtype=np.int32),
    np.zeros(0, dtype=np.int8),
    [],                                                  # an empty list
    [2, 4, 1],
], ids=["pads", "outside", "int32", "empty", "empty-list", "list"])
def test_decode_bases_is_the_per_base_loop_in_one_pass(codes):
    """`decode_bases` is one array pass; the per-base loop it replaced
    stays here as what it must equal."""
    assert decode_bases(codes) == "".join(
        "ACGT"[c] for c in np.asarray(codes) if 0 <= c < 4)
