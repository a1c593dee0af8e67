"""Compile the main-path device programs for a described TPU v5e, no chip.

The TPU compiler is installed alongside jax and compiles for a topology
that is described rather than attached, so these cases raise what the
chip's compiler would raise (fast-memory limit, unaligned slice, HBM)
at the real bucket shapes -- the things interpret-mode tests cannot see.
Nothing runs: a pass here is not a chip run (chip_smoke.py is).

This is the only file that describes the chip.  The description happens
inside the `topo` fixture, never at import: only one process may load
the TPU library, and under pytest-xdist every worker imports every test
file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024 ** 3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip; conftest.py turns the
    cache on, so turn it off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(tree, sharding):
    """Shapes of `tree` placed on the described chip."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _read_shapes(n: int, jmax: int):
    """Flat read-batch arguments of the fill / dense kernels."""
    from pbccs_tpu.parallel.batch import _imax_bucket

    s = jax.ShapeDtypeStruct
    imax = _imax_bucket(jmax)
    return (s((n, imax), jnp.int8),        # reads
            s((n,), jnp.int32),            # rlens
            s((n, jmax), jnp.int8),        # window templates
            s((n, jmax, 4), jnp.float32),  # window transitions
            s((n,), jnp.int32))            # window lengths


def _fill_case(n, jmax, width):
    from pbccs_tpu.models.arrow.scorer import fill_alpha_beta_batch

    def fn(*a):
        return fill_alpha_beta_batch(*a, width, use_pallas=True)

    return fn, _read_shapes(n, jmax)


def _dense_case(n, jmax, width):
    from pbccs_tpu.models.arrow.scorer import fill_alpha_beta_batch
    from pbccs_tpu.ops.dense_score_pallas import dense_interior_scores_batch

    reads = _read_shapes(n, jmax)
    alpha, beta, _, _, apre, bsuf = jax.eval_shape(
        lambda *a: fill_alpha_beta_batch(*a, width, use_pallas=True), *reads)
    tables = jax.ShapeDtypeStruct((n, 8, 4), jnp.float32)

    def fn(*a):
        return dense_interior_scores_batch(*a, width)

    return fn, reads + (tables, alpha, beta, apre, bsuf)


def _totals_case(z, r, jmax):
    """slot_grid_totals, the pass after the dense kernel, on a bucket's
    slot-major score grid."""
    from pbccs_tpu.ops.dense_score_pallas import slot_grid_totals

    s = jax.ShapeDtypeStruct
    per_read = lambda dtype: s((z * r,), dtype)
    plane = lambda dtype: s((9, jmax), dtype)
    return slot_grid_totals, (
        s((z * r, 9, jmax), jnp.float32),           # slot-major grid
        per_read(jnp.int32), per_read(jnp.int32), per_read(jnp.int32),
        per_read(jnp.bool_), per_read(jnp.float32),  # live, baselines
        s((z, 9, jmax), jnp.bool_),                  # valid slots
        plane(jnp.int32), plane(jnp.int32), plane(jnp.bool_))


def _bucket_shapes(z, r, jmax):
    from pbccs_tpu.parallel.batch import _imax_bucket

    s = jax.ShapeDtypeStruct
    return (s((z, jmax), jnp.int8),                   # template tracks
            s((z,), jnp.int32),                       # template lengths
            s((z, 8, 4), jnp.float32),                # host transition tables
            s((z, r, _imax_bucket(jmax)), jnp.int8),  # reads
            s((z, r), jnp.int32),                     # rlens
            s((z, r), jnp.int32),                     # strands
            s((z, r), jnp.int32),                     # tstarts
            s((z, r), jnp.int32))                     # tends


def _bucket_statics(jmax):
    from pbccs_tpu.models.arrow.params import (BandingOptions,
                                               effective_band_width)
    from pbccs_tpu.models.arrow.scorer import guided_fill_passes

    return (effective_band_width(BandingOptions(), jmax),
            guided_fill_passes(jmax))


def _bucket_case(z, r, jmax):
    from pbccs_tpu.parallel import batch

    width, guided = _bucket_statics(jmax)

    def fn(*a):
        # as BatchPolisher launches it: the lanes that hold a read named
        return batch.lowering_target()(*a[:-1], width, use_pallas=True,
                                       mesh=None, guided_passes=guided,
                                       real_rows=a[-1])

    return fn, _bucket_shapes(z, r, jmax) + (
        jax.ShapeDtypeStruct((z, r), jnp.bool_),)


def _refine_loop_case(z, r, jmax):
    """run_refine_loop as BatchPolisher.refine_device launches it on the
    chip (dense scoring, Pallas fills), on the state _loop_state builds
    from the bucket program's outputs."""
    from pbccs_tpu.models.arrow.refine import RefineOptions
    from pbccs_tpu.parallel import batch
    from pbccs_tpu.parallel import device_refine as dr

    width, guided = _bucket_statics(jmax)
    tpl, tlens, tables, reads, rlens, strands, tstarts, tends = \
        _bucket_shapes(z, r, jmax)

    def loop_state(*a):
        tpl, tlens, tables, reads, rlens, strands, tstarts, tends = a
        (win_tpl, win_trans, wlens, alpha, beta, ll_a, ll_b, apre, bsuf,
         trans_f, tpl_r, trans_r, _table, _mu, _var) = \
            batch.lowering_target()(*a, width, use_pallas=True, mesh=None,
                                    guided_passes=guided)
        return dr.RefineLoopState(
            tpl=tpl, tlens=tlens, tstarts=tstarts, tends=tends,
            win_tpl=win_tpl, win_trans=win_trans, wlens=wlens,
            alpha=alpha, beta=beta, a_prefix=apre, b_suffix=bsuf,
            baselines=ll_b, trans_f=trans_f, tpl_r=tpl_r, trans_r=trans_r,
            active=jnp.ones((z, r), bool), it=jnp.int32(0),
            done=jnp.zeros(z, bool), converged=jnp.zeros(z, bool),
            iterations=jnp.zeros(z, jnp.int32),
            n_tested=jnp.zeros(z, jnp.int32),
            n_applied=jnp.zeros(z, jnp.int32),
            allowed=jnp.ones((z, jmax), bool),
            history=jnp.zeros((z, 48), jnp.uint32),
            hist_n=jnp.zeros(z, jnp.int32), overflow=jnp.asarray(False),
            dlayout=dr.state_layout(reads, rlens, win_tpl, win_trans, wlens,
                                    tables, alpha, beta, apre, bsuf,
                                    width=width),
            # as batch._loop_state: a loop with no straggler exit watches
            # for ZMWs whose rounds repeat
            cycle=None if dr.straggler_exit_zmws(z)
            else dr.new_cycle_watch(z, 48))

    state = jax.eval_shape(loop_state, tpl, tlens, tables, reads, rlens,
                           strands, tstarts, tends)
    opts = RefineOptions()

    def fn(*a):
        return dr.run_refine_loop(
            *a, width=width, use_pallas=True,
            max_iterations=opts.max_iterations,
            separation=opts.mutation_separation,
            neighborhood=opts.mutation_neighborhood,
            chunk=batch.MUT_CHUNK, min_fast_edge=batch.MIN_FAST_EDGE_WLEN,
            dense=True, guided_passes=guided)

    real_rows = jax.ShapeDtypeStruct((z, r), jnp.bool_)
    return fn, (state, reads, rlens, strands, tables, real_rows)


CASES = [
    pytest.param(_fill_case, (256, 576, 64), id="fill-256x576xW64"),
    pytest.param(_fill_case, (256, 2112, 96), id="fill-256x2112xW96"),
    # the 2x-band mating retry of a 2 kb batch (pipeline._polish_batch_arrow)
    pytest.param(_fill_case, (64, 2240, 192), id="fill-64x2240xW192-reband"),
    pytest.param(_dense_case, (256, 576, 64), id="dense-256x576xW64"),
    pytest.param(_dense_case, (256, 2112, 96), id="dense-256x2112xW96"),
    pytest.param(_dense_case, (96, 15104, 96), id="dense-96x15104xW96"),
    # the 15 kb x 3 bucket's score grid (never run on the chip)
    pytest.param(_totals_case, (32, 3, 15104), id="totals-32x3x15104"),
    pytest.param(_bucket_case, (32, 10, 2112), id="bucket-32x10x2112"),
    pytest.param(_bucket_case, (128, 8, 576), id="bucket-128x8x576"),
    pytest.param(_refine_loop_case, (32, 10, 2112),
                 id="refine_loop-32x10x2112"),
    # a part of the ragged cell slice's chunk: 32 lanes, the governor's
    # ceiling Z = 8, no straggler exit, so with the cycle watch
    pytest.param(_refine_loop_case, (8, 32, 2304),
                 id="refine_loop-8x32x2304-cycle_watch"),
]


@pytest.mark.parametrize("build,shape", CASES)
def test_compiles_for_v5e(build, shape, one_chip, no_persistent_cache,
                          monkeypatch):
    from pbccs_tpu.ops import dense_score_pallas, fwdbwd_pallas

    # the process's backend is the CPU, where both kernels would choose
    # interpret mode; compile the real kernels
    monkeypatch.setattr(fwdbwd_pallas, "_interpret", lambda: False)
    monkeypatch.setattr(dense_score_pallas, "_interpret", lambda: False)
    jax.clear_caches()  # no trace made in interpret mode is reused

    try:
        fn, shapes = build(*shape)
        compiled = jax.jit(fn).lower(*_on(shapes, one_chip)).compile()
    finally:
        jax.clear_caches()  # nor does a later test reuse these

    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, (
        f"{total / 2**30:.2f} GiB of arguments + outputs + temps does not "
        f"fit one v5e chip: {mem}")


# --------------------------------------------------------------------------
# the loop program moves no band only to change its order, origin or padding
# --------------------------------------------------------------------------

# Band-sized layout-only instructions the optimised loop program may hold:
# (opcode, where it comes from (op_name under run_refine_loop), how many, why).
# Anything else of a band tensor's element count or more fails the guard.
# Inside the rebuild's passes (_REBUILD_PASS: scorer.for_needed_reads' loop
# in the rebuild's branch) a band tensor is a pass's, _FILL_CHUNK reads'.
_REBUILD_PASS = r"^while/body/cond/branch_1_fun/while/body/"
ALLOWED_BAND_LAYOUT_OPS = [
    ("copy", r"^$", 10,
     "the program's boundary, once a dispatch: arrays cross jitted programs "
     "in the device's default layout, column-minor where W < 128 lanes, and "
     "the kernels take row-major (4 bands in, 4 out; and, in and out, the "
     "72-lane patch plane where W = 64 makes it band-sized)"),
    ("copy", r"^while/body/cond$", 1,
     "W = 64 only: the 72-lane patch plane leaves the rebuild's branch"),
    ("copy", _REBUILD_PASS + r"transpose$", 1,
     "the alpha fill's read windows, computed read-major with the layout's "
     "and turned columns-leading for the coefficients (the kernel loads a "
     "column by address arithmetic on its untiled leading axis)"),
    ("copy", _REBUILD_PASS + r"vmap\(\)/dot_general$", 2,
     "the beta fill: the two halves of its own window matmul, turned before "
     "its coefficients are computed (sharing the alpha fill's windows through "
     "a reverse read wrong on the chip: fwdbwd_pallas._backward_coeffs)"),
    ("pad", _REBUILD_PASS + r"concatenate$", 7,
     "inside fusions, arithmetic: band_read_windows' lane rotation and "
     "previous-column shift (a roll is a concatenate of two slices)"),
    ("concatenate", _REBUILD_PASS + r"vmap\(\)/concatenate$", 2,
     "the bf16 im2col of the reads, the window matmuls' operand (one for "
     "read base i, one for base i-1)"),
]
_LAYOUT_OPCODES = ("copy", "transpose", "pad", "concatenate", "slice")


def band_layout_ops(hlo_text: str, floor: int, pass_floor: int | None = None):
    """(opcode, op_name under the loop program) of every layout-only HLO
    instruction, fused or not, whose output has `floor` elements or more
    (`pass_floor` or more inside the rebuild's passes).  The Pallas
    kernels are opaque custom calls: nothing inside them counts."""
    import re

    shape = re.compile(r"\b(?:pred|[suf]\d+|bf16)\[([\d,]*)\]")
    found = []
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z\-]+)\(", line)
        if not m or m.group(2) not in _LAYOUT_OPCODES:
            continue
        sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                 for dims in shape.findall(m.group(1))]
        name = re.search(r'op_name="([^"]*)"', line)
        where = re.sub(r"^jit\(fn\)/jit\(run_refine_loop\)/?", "",
                       name.group(1) if name else "")
        in_pass = pass_floor is not None and re.match(_REBUILD_PASS, where)
        if max(sizes, default=0) < (pass_floor if in_pass else floor):
            continue
        found.append((m.group(2), where))
    return found


_LOOP_HLO: dict = {}


def _loop_hlo(z, r, jmax, one_chip) -> str:
    """Optimised HLO of run_refine_loop at one shape set, real kernels,
    for the described chip; compiled once for the tests that read it."""
    from pbccs_tpu.ops import dense_score_pallas, fwdbwd_pallas

    if (z, r, jmax) not in _LOOP_HLO:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(fwdbwd_pallas, "_interpret", lambda: False)
            mp.setattr(dense_score_pallas, "_interpret", lambda: False)
            jax.clear_caches()
            try:
                fn, shapes = _refine_loop_case(z, r, jmax)
                _LOOP_HLO[z, r, jmax] = jax.jit(fn).lower(
                    *_on(shapes, one_chip)).compile().as_text()
            finally:
                jax.clear_caches()
    return _LOOP_HLO[z, r, jmax]


@pytest.mark.parametrize("z,r,jmax", [(32, 32, 576), (32, 12, 2304)],
                         ids=["500bp-32x32x576", "2kb-32x12x2304"])
def test_loop_program_rewrites_no_band(z, r, jmax, one_chip,
                                       no_persistent_cache):
    """In the optimised HLO of run_refine_loop at the cells' shape sets no
    copy, transpose, pad, concatenate or slice outside the Pallas calls
    has an output of a band tensor's element count or more, but those on
    ALLOWED_BAND_LAYOUT_OPS.  This is what keeps the transposes, halo
    copies, pads and per-round relayouts PR 28 took out from coming back."""
    import collections
    import re

    from pbccs_tpu.models.arrow import scorer
    from pbccs_tpu.ops import fwdbwd

    text = _loop_hlo(z, r, jmax, one_chip)
    width, _ = _bucket_statics(jmax)
    a_read = fwdbwd.band_frame_rows(jmax + 1) * width
    seen = collections.Counter(band_layout_ops(
        text, z * r * a_read, min(z * r, scorer._FILL_CHUNK) * a_read))
    # the rebuild's passes are in sight: their im2col is found
    assert any(re.match(_REBUILD_PASS, where) for _, where in seen)
    # two fills, dense, edge rows; four place_reads a pass
    assert text.count("tpu_custom_call") >= 8
    for (opcode, where), n in sorted(seen.items()):
        allowed = [cap for op, rx, cap, _ in ALLOWED_BAND_LAYOUT_OPS
                   if op == opcode and re.search(rx, where)]
        assert allowed and n <= max(allowed), (
            f"{n} band-sized `{opcode}` from {where or 'the program boundary'!r} "
            f"in the loop program at {z}x{r}x{jmax}: not on "
            "ALLOWED_BAND_LAYOUT_OPS (or more than it allows)")


# --------------------------------------------------------------------------
# the score grid is slot-major after the dense kernel, and mapped by no gather
# --------------------------------------------------------------------------

_INSTR = (r"^\s*(?:ROOT )?%?([\w.\-]+) = [a-z]\w*\[([\d,]*)\]"
          r"(?:\{([\d,]*)[^}]*\})? ([a-z\-]+)\((.*)")


def unfused_instructions(hlo_text: str):
    """(name, dims, minor dimension's size, opcode, rest of the line) of
    every array-valued instruction outside the fused computations: what
    is written to memory.  The minor dimension is the layout's, the one
    the lanes hold."""
    import re

    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo_text))
    comp = None
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(_INSTR, line)
        if not m or comp in fused:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if not dims:
            continue
        order = [int(d) for d in m.group(3).split(",")] if m.group(3) \
            else list(range(len(dims)))[::-1]
        yield m.group(1), dims, dims[order[0]], m.group(4), m.group(5)


def gather_index_counts(hlo_text: str) -> list:
    """Indices of every gather, fused or not: its output's elements over
    its slice's."""
    import re

    counts = []
    for line in hlo_text.splitlines():
        m = re.match(_INSTR, line)
        if not m or m.group(4) != "gather":
            continue
        out = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
        sl = re.search(r"slice_sizes=\{([\d,]*)\}", line).group(1)
        counts.append(out // int(np.prod([int(d) for d in sl.split(",")])))
    return counts


@pytest.mark.parametrize("z,r,jmax",
                         [(16, 12, 2304), (32, 12, 2304), (32, 32, 576)],
                         ids=["serve-16x12x2304", "2kb-32x12x2304",
                              "500bp-32x32x576"])
def test_loop_program_keeps_the_score_grid_slot_major(z, r, jmax, one_chip,
                                                      no_persistent_cache):
    """In the optimised HLO of run_refine_loop at the three cells' shape
    sets: no gather of Z*R*Jmax indices or more (the orientation mapping
    was three a round, nine floats an index); the dense kernel's output is
    read by one instruction; nothing but the kernel and that instruction
    writes a grid-sized array with the nine slots on the lanes (it tiles
    to 128: 14x the bytes); and the grid is turned once, not copied from
    layout to layout.  This is what keeps the gathers and the 9-lane
    passes PR 34 took out from coming back."""
    import re

    text = _loop_hlo(z, r, jmax, one_chip)
    counts = gather_index_counts(text)
    assert counts, "the edge program's gathers are in sight"
    assert max(counts) < z * r * jmax, (
        f"a gather of {max(counts)} indices in the loop program at "
        f"{z}x{r}x{jmax}: the score grid's mapping is rolls and selects")

    grid = z * r * jmax * 9
    instrs = list(unfused_instructions(text))
    dense = [name for name, dims, _, op, _ in instrs
             if op == "custom-call" and dims == [z * r, jmax, 9]
             and name.startswith("dense_interior_scores_batch")]
    assert len(dense) == 1, "the loop's one dense score call is in sight"
    readers = [name for name, _, _, _, rest in instrs
               if re.search(rf"%{re.escape(dense[0])}\b", rest)]
    assert len(readers) == 1, (
        f"the dense kernel's output is read by {readers}: it is read once, "
        "by what turns it slot-major")
    on_lanes = [name for name, dims, minor, _, _ in instrs
                if minor == 9 and int(np.prod(dims)) >= grid
                and name not in dense + readers]
    assert not on_lanes, (
        f"{on_lanes} write a grid-sized array with the 9 slots on the lanes")
    turns = [name for name, dims, _, op, _ in instrs
             if op == "copy" and 9 in dims and int(np.prod(dims)) >= grid]
    assert len(turns) <= 1, (
        f"the score grid is copied between layouts {len(turns)} times: "
        f"{turns}")
