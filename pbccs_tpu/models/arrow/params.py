"""Arrow model parameters: SNR-conditioned dinucleotide transition model.

The Arrow pair-HMM conditions its per-template-position transition
probabilities {Match, Branch, Stick, Dark(=deletion)} on the dinucleotide
context (current base, next base) and the per-channel signal-to-noise ratio of
the ZMW.  Eight contexts exist: homopolymer contexts AA/CC/GG/TT (next base
equals current) and generic contexts NA/NC/NG/NT.  For each context a trained
3x4 coefficient matrix maps [1, snr, snr^2, snr^3] of the *next* base's
channel SNR through a softmax-with-reference to the four probabilities.

Behavioral parity target: ConsensusCore Arrow ContextParameterProvider
(reference ConsensusCore/src/C++/Arrow/ContextParameterProvider.cpp:23-113)
and TemplateParameterPair construction (TemplateParameterPair.cpp:43-60).
The coefficient tables below are the reference's trained model constants
(model *data*, equivalent to shipped weights).

TPU-first design: instead of a per-position hash-map lookup, the whole
template's transition-parameter track is computed as one vectorized gather +
polynomial evaluation over an int8 base tensor, jit/vmap friendly.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from pbccs_tpu.runtime import tuning as _tuning

# Base encoding used framework-wide: A=0 C=1 G=2 T=3, padding/invalid = 4.
BASE_A, BASE_C, BASE_G, BASE_T, BASE_PAD = 0, 1, 2, 3, 4
N_BASES = 4
BASES = "ACGT"

_BASE_LUT = np.full(256, BASE_PAD, dtype=np.int8)
for _i, _b in enumerate(BASES):
    _BASE_LUT[ord(_b)] = _i
    _BASE_LUT[ord(_b.lower())] = _i


def encode_bases(seq: str) -> np.ndarray:
    """ASCII sequence -> int8 codes (A=0 C=1 G=2 T=3, other=4)."""
    return _BASE_LUT[np.frombuffer(seq.encode("ascii"), dtype=np.uint8)]


_BASE_LETTERS = np.frombuffer(BASES.encode("ascii"), dtype="S1")


def decode_bases(codes: np.ndarray) -> str:
    """int8 codes -> ASCII sequence. Pad codes (>=4) are dropped.  One
    array pass: a result's sequence is decoded on the thread that owns
    the device (`polish.finish`), 0.3 ms a 2 kb template as a loop."""
    codes = np.asarray(codes)
    kept = codes[(codes >= 0) & (codes < 4)].astype(np.intp)
    return _BASE_LETTERS[kept].tobytes().decode("ascii")


_COMPLEMENT = np.array([3, 2, 1, 0, 4], dtype=np.int8)


def revcomp(codes: np.ndarray) -> np.ndarray:
    """Reverse complement of an int8 base vector (pads map to pad)."""
    return _COMPLEMENT[np.asarray(codes)[::-1]]


def revcomp_padded(tpl: "jax.Array", length: "jax.Array") -> "jax.Array":
    """Jittable reverse complement of the first `length` entries of a padded
    int8 template; the tail stays padding (code 4)."""
    Jmax = tpl.shape[0]
    idx = length - 1 - jnp.arange(Jmax, dtype=jnp.int32)
    comp = jnp.asarray(_COMPLEMENT)
    vals = comp[jnp.take(tpl, jnp.clip(idx, 0, Jmax - 1)).astype(jnp.int32)]
    return jnp.where(idx >= 0, vals, 4).astype(jnp.int8)


# Transition-probability channel order used framework-wide.
TRANS_MATCH, TRANS_BRANCH, TRANS_STICK, TRANS_DARK = 0, 1, 2, 3

# Trained SNR-polynomial coefficients.  ctx index = next_base + 4*(cur != next)
# i.e. 0..3 = AA,CC,GG,TT ; 4..7 = NA,NC,NG,NT.   Per context: rows are the
# softmax numerators [Dark, Match, Stick] (Branch is the softmax reference),
# columns are [1, snr, snr^2, snr^3] of the next base's channel SNR.
# Values: reference ContextParameterProvider.cpp:23-66 (trained model data).
CONTEXT_COEFF = np.array(
    [
        [  # AA
            [3.76122480667588, -0.536010820176981, 0.0275375059387171, -0.000470200724345621],
            [3.57517725358548, -0.0257545295375707, -0.000163673803286944, 5.3256984681724e-06],
            [0.858421613302247, -0.0276654216841666, -8.85549766507732e-05, -4.85355908595337e-05],
        ],
        [  # CC
            [5.66725538674764, -1.10462196933913, 0.0879811093908922, -0.00259393800835979],
            [4.11682756767018, -0.124758322644639, 0.00659795177909886, -0.000361914629195461],
            [3.17103818507405, -0.729020290806687, 0.0749784690396837, -0.00262779517495421],
        ],
        [  # GG
            [3.81920778703052, -0.540309003502589, 0.0389569264893982, -0.000901245733796236],
            [3.31322216145728, 0.123514009118836, -0.00807401406655071, 0.000230843924466035],
            [2.06006877520527, -0.451486652688621, 0.0375212898173045, -0.000937676250926241],
        ],
        [  # TT
            [5.39308368236762, -1.32931568057267, 0.107844580241936, -0.00316462903462847],
            [4.21031404956015, -0.347546363361823, 0.0293839179303896, -0.000893802212450644],
            [2.33143889851302, -0.586068444099136, 0.040044954697795, -0.000957298861394191],
        ],
        [  # NA
            [2.35936060895653, -0.463630601682986, 0.0179206897766131, -0.000230839937063052],
            [3.22847830625841, -0.0886820214931539, 0.00555981712798726, -0.000137686231186054],
            [-0.101031042923432, -0.0138783767832632, -0.00153408019582419, 7.66780338484727e-06],
        ],
        [  # NC
            [5.956054206161, -1.71886470811695, 0.153315470604752, -0.00474488595513198],
            [3.89418464416296, -0.174182841558867, 0.0171719290275442, -0.000653629721359769],
            [2.40532887070852, -0.652606650098156, 0.0688783864119339, -0.00246479494650594],
        ],
        [  # NG
            [3.53508304630569, -0.788027301381263, 0.0469367803413207, -0.00106221924705805],
            [2.85440184222226, 0.166346531056167, -0.0166161828155307, 0.000439492705370092],
            [0.238188180807376, 0.0589443522886522, -0.0123401045958974, 0.000336854126836293],
        ],
        [  # NT
            [5.36199280681367, -1.46099908985536, 0.126755291030074, -0.0039102734460725],
            [3.41597143103046, -0.066984162951578, 0.0138944877787003, -0.000558939998921912],
            [1.37371376794871, -0.246963827944892, 0.0209674231346363, -0.000684856715039738],
        ],
    ],
    dtype=np.float64,
)

# Hard-coded trained miscall probability (reference Arrow/ArrowConfig.hpp:52).
MISMATCH_PROBABILITY = 0.00505052456472967


@dataclasses.dataclass(frozen=True)
class ModelParams:
    """Scalar emission parameters of the Arrow HMM.

    Parity: reference Arrow/ArrowConfig.hpp:85-113 (the IQV PMFs there are
    all-ones placeholders, so they are omitted here; re-add as a per-read
    emission track if ever trained).
    """

    pr_miscall: float = MISMATCH_PROBABILITY

    @property
    def pr_not_miscall(self) -> float:
        return 1.0 - self.pr_miscall

    @property
    def pr_third_of_miscall(self) -> float:
        return self.pr_miscall / 3.0


@dataclasses.dataclass(frozen=True)
class BandingOptions:
    """Banded-DP budget. score_diff is in nats (reference BandingOptions;
    pbccs passes 12.5, include/pacbio/ccs/Consensus.h:438).  On TPU the
    adaptive per-column band becomes a static band of `band_width` rows per
    column centered on the main diagonal; `score_diff` is retained for the
    band-adequacy (alpha/beta mismatch) check semantics."""

    score_diff: float = 12.5
    #: None = the per-length-bucket schedule (effective_band_width); an
    #: explicit width always wins (the 2x mating retry relies on this).
    band_width: int | None = None


def effective_band_width(banding: "BandingOptions", jmax: int) -> int:
    """Per-length-bucket band width schedule.

    The round-4 banding counters showed mean band occupancy ~0.60 at every
    short config -- W=96 wastes ~40% of band compute at <=576-column
    buckets -- while long templates need guided rebanding rather than more
    width (ops/fwdbwd.guided_band_offsets).  The schedule runs W=64 at
    short buckets, W=96 above.  An explicitly configured band_width always
    wins (so the pipeline's 2x mating retry escalates the width it asks
    for, even under the env override); PBCCS_BAND_W replaces the
    schedule's default choice only.

    Long buckets (> 8192) run W=96, occupancy-driven (round 6): the
    round-5 schedule ran them at W=128 because the alignment drift after
    a big apply round clipped the W=96 band at the round-1 rebuild with
    TWO guided passes -- one read unmated and the ZMW ran away on weak
    evidence (+834 bases, bucket overflow, round-5 bench draw).  But the
    measured cost of the width was real: cfg3's 15 kb band occupancy was
    0.465 at W=128, i.e. more than half the band
    compute, VMEM, and HBM traffic polished empty lanes.  The round-6
    schedule fixes the CAUSE instead of widening around it: long buckets
    run a THIRD argmax-guided refill pass (scorer.guided_fill_passes),
    which re-centers the band on the post-apply path the round-5 failure
    drifted off, and keep W=96.  The mating gate still protects
    correctness (a clipped read drops or triggers the 2x retry, whose
    explicit band_width bypasses this schedule).  PBCCS_BAND_W replaces
    the schedule's choice for A/B measurement.

    The reference's analogue is the adaptive per-column band itself
    (SimpleRecursor.cpp:693-757), which sizes effort to the data; a static
    schedule keyed on the compile-time bucket plus guided re-centering is
    the XLA-friendly form."""
    if banding.band_width is not None:
        return banding.band_width
    env = os.environ.get("PBCCS_BAND_W")
    if env:
        return int(env)
    # tuned-profile default (runtime/tuning.py resolution ladder): an
    # applied `ccs tune` host profile replaces the schedule's choice,
    # exactly like PBCCS_BAND_W but measured instead of hand-picked
    tuned = _tuning.knob_int("band_w")
    if tuned is not None:
        return tuned
    return 64 if jmax <= 576 else 96


@dataclasses.dataclass(frozen=True)
class ArrowConfig:
    """Parity: reference Arrow/ArrowConfig.hpp:112-129."""

    model: ModelParams = dataclasses.field(default_factory=ModelParams)
    banding: BandingOptions = dataclasses.field(default_factory=BandingOptions)
    fast_score_threshold: float = -12.5
    add_threshold: float = float("nan")


def snr_to_transition_table(snr: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Per-ZMW (8, 4) table of transition probabilities from channel SNRs.

    snr: (4,) per-channel SNR in A,C,G,T order.
    Returns table[ctx, {match, branch, stick, dark}], natural scale.

    Parity: ContextParameterProvider::GetTransitionParameters
    (reference ContextParameterProvider.cpp:69-113): numerators
    exp(poly([Dark, Match, Stick])) with Branch the implicit reference
    (numerator 1); probabilities are the softmax over the four.
    """
    snr = jnp.asarray(snr, dtype=jnp.float32)
    coeff = jnp.asarray(CONTEXT_COEFF, dtype=jnp.float32)  # (8, 3, 4)
    # channel of ctx k is (k mod 4): the *next* base of the dinucleotide.
    chan_snr = jnp.tile(snr, 2)  # (8,)
    powers = chan_snr[:, None] ** jnp.arange(4, dtype=jnp.float32)  # (8, 4)
    xb = jnp.exp(jnp.einsum("crp,cp->cr", coeff, powers))  # (8, 3) = Dark,Match,Stick
    denom = 1.0 + jnp.sum(xb, axis=-1)  # (8,)
    dark = xb[:, 0] / denom
    match = xb[:, 1] / denom
    stick = xb[:, 2] / denom
    branch = 1.0 / denom
    return jnp.stack([match, branch, stick, dark], axis=-1).astype(dtype)


def snr_to_transition_table_host(snr: np.ndarray) -> np.ndarray:
    """Float64 host evaluation of snr_to_transition_table.

    The reference evaluates the SNR polynomial + softmax in double
    (ContextParameterProvider.cpp:69-113); in float32 the exp(cubic) is
    sensitive to op ordering, so eager vs jit/vmap evaluation of the jnp
    version can disagree by ~0.4% per probability — enough to shift window
    log-likelihoods by ~0.1 nat.  The table is tiny (8x4 per ZMW), so both
    the per-ZMW and batched scorers compute it here, on host, in float64,
    and feed the result into their jitted programs."""
    snr = np.asarray(snr, np.float64)
    chan_snr = np.tile(snr, 2)  # (8,)
    powers = chan_snr[:, None] ** np.arange(4)  # (8, 4)
    xb = np.exp(np.einsum("crp,cp->cr", CONTEXT_COEFF, powers))  # Dark,Match,Stick
    denom = 1.0 + xb.sum(axis=-1)
    return np.stack(
        [xb[:, 1] / denom, 1.0 / denom, xb[:, 2] / denom, xb[:, 0] / denom],
        axis=-1,
    )


def context_index(cur_base: jax.Array, next_base: jax.Array) -> jax.Array:
    """Dinucleotide context id: next_base + 4 * (cur != next).

    Parity: ContextParameters context-string construction ("AA".."TT" when the
    bases repeat else "N"+next; reference ContextParameters.cpp /
    GetParametersForContext)."""
    return next_base + 4 * (cur_base != next_base).astype(next_base.dtype)


def transition_lookup(cur_base: jax.Array, next_base: jax.Array,
                      table: jax.Array) -> jax.Array:
    """(..., 4) transition rows for dinucleotide contexts, as a one-hot
    matmul on the MXU — the gather form (table[ctx]) lowers to the TPU
    scalar core.  Single source of truth for the clip bounds / dtype /
    precision flags (oriented_window and dense_patch_grids both ride it;
    eager-vs-jit table evaluation drift caused a ~0.1-nat parity bug
    once)."""
    idx = jnp.clip(context_index(cur_base.astype(jnp.int32),
                                 next_base.astype(jnp.int32)), 0, 7)
    onehot = (idx[..., None] == jnp.arange(8)).astype(jnp.float32)
    return jax.lax.dot_general(
        onehot, table.astype(jnp.float32),
        (((onehot.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def template_transition_params(
    tpl: jax.Array, trans_table: jax.Array, length: jax.Array | None = None
) -> jax.Array:
    """Per-position transition track for a template.

    tpl: (L,) int8 base codes (possibly padded).
    trans_table: (8, 4) from snr_to_transition_table.
    length: actual template length (traced scalar) if tpl is padded.

    Returns (L, 4) [match, branch, stick, dark]; position i conditions on
    (tpl[i], tpl[i+1]).  The final position's params are zero, matching the
    reference's sentinel (TemplateParameterPair.cpp:56-58) -- they are never
    read by the recursion.
    """
    tpl = jnp.asarray(tpl)
    L = tpl.shape[0]
    nxt = jnp.roll(tpl, -1)
    ctx = context_index(tpl.astype(jnp.int32), nxt.astype(jnp.int32))
    params = trans_table[jnp.clip(ctx, 0, 7)]  # (L, 4)
    if length is None:
        last = L - 1
    else:
        last = length - 1
    pos = jnp.arange(L)
    valid = pos < last
    return jnp.where(valid[:, None], params, 0.0)
