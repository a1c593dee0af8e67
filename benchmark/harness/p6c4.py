"""P6-C4 Arrow model constants, copied into the benchmark.

The traffic generator and the plain reference read these, never the
program's `pbccs_tpu/models/arrow/params.py`: a change to the program's
tables must not change the traffic or the yardstick.  Values are the
trained model data of ConsensusCore (Arrow/ContextParameterProvider.cpp:23-66,
Arrow/ArrowConfig.hpp:52), as `params.py` carried them at PR 23.

Base codes: A=0 C=1 G=2 T=3.  Transition channels: match, branch, stick,
dark (deletion).  Context index = next_base + 4 * (cur != next); per
context the rows are the softmax numerators [dark, match, stick] (branch
is the reference, numerator 1) and the columns the powers 0..3 of the
next base's channel SNR.
"""

import numpy as np

BASES = "ACGT"
MATCH, BRANCH, STICK, DARK = 0, 1, 2, 3
PR_MISCALL = 0.00505052456472967

CONTEXT_COEFF = np.array([
    [[3.76122480667588, -0.536010820176981, 0.0275375059387171, -0.000470200724345621],
     [3.57517725358548, -0.0257545295375707, -0.000163673803286944, 5.3256984681724e-06],
     [0.858421613302247, -0.0276654216841666, -8.85549766507732e-05, -4.85355908595337e-05]],
    [[5.66725538674764, -1.10462196933913, 0.0879811093908922, -0.00259393800835979],
     [4.11682756767018, -0.124758322644639, 0.00659795177909886, -0.000361914629195461],
     [3.17103818507405, -0.729020290806687, 0.0749784690396837, -0.00262779517495421]],
    [[3.81920778703052, -0.540309003502589, 0.0389569264893982, -0.000901245733796236],
     [3.31322216145728, 0.123514009118836, -0.00807401406655071, 0.000230843924466035],
     [2.06006877520527, -0.451486652688621, 0.0375212898173045, -0.000937676250926241]],
    [[5.39308368236762, -1.32931568057267, 0.107844580241936, -0.00316462903462847],
     [4.21031404956015, -0.347546363361823, 0.0293839179303896, -0.000893802212450644],
     [2.33143889851302, -0.586068444099136, 0.040044954697795, -0.000957298861394191]],
    [[2.35936060895653, -0.463630601682986, 0.0179206897766131, -0.000230839937063052],
     [3.22847830625841, -0.0886820214931539, 0.00555981712798726, -0.000137686231186054],
     [-0.101031042923432, -0.0138783767832632, -0.00153408019582419, 7.66780338484727e-06]],
    [[5.956054206161, -1.71886470811695, 0.153315470604752, -0.00474488595513198],
     [3.89418464416296, -0.174182841558867, 0.0171719290275442, -0.000653629721359769],
     [2.40532887070852, -0.652606650098156, 0.0688783864119339, -0.00246479494650594]],
    [[3.53508304630569, -0.788027301381263, 0.0469367803413207, -0.00106221924705805],
     [2.85440184222226, 0.166346531056167, -0.0166161828155307, 0.000439492705370092],
     [0.238188180807376, 0.0589443522886522, -0.0123401045958974, 0.000336854126836293]],
    [[5.36199280681367, -1.46099908985536, 0.126755291030074, -0.0039102734460725],
     [3.41597143103046, -0.066984162951578, 0.0138944877787003, -0.000558939998921912],
     [1.37371376794871, -0.246963827944892, 0.0209674231346363, -0.000684856715039738]],
], dtype=np.float64)


def transition_table(snr) -> np.ndarray:
    """(8 contexts, 4 channels) transition probabilities of one ZMW, f64."""
    chan = np.tile(np.asarray(snr, np.float64), 2)
    powers = chan[:, None] ** np.arange(4)
    xb = np.exp(np.einsum("crp,cp->cr", CONTEXT_COEFF, powers))
    denom = 1.0 + xb.sum(axis=1)
    return np.stack([xb[:, 1] / denom, 1.0 / denom, xb[:, 2] / denom,
                     xb[:, 0] / denom], axis=1)


def transition_track(tpl: np.ndarray, table: np.ndarray) -> np.ndarray:
    """(J, 4) moves leaving each template position; the last row is zero
    (nothing leaves the pinned last base)."""
    tpl = np.asarray(tpl, np.int64)
    track = np.zeros((len(tpl), 4))
    ctx = tpl[1:] + 4 * (tpl[:-1] != tpl[1:])
    track[:-1] = table[ctx]
    return track


def revcomp(codes: np.ndarray) -> np.ndarray:
    return (3 - np.asarray(codes))[::-1].astype(np.int8)


def encode(seq: str) -> np.ndarray:
    lut = np.full(256, 4, np.int8)
    for i, b in enumerate(BASES):
        lut[ord(b)] = i
    return lut[np.frombuffer(seq.encode("ascii"), np.uint8)]


def decode(codes: np.ndarray) -> str:
    return np.frombuffer(b"ACGT", "S1")[np.asarray(codes, np.int64)].tobytes().decode()
