import pytest

from harness import manifest, roofline

FILL = manifest.load_by_path("kernels", "fill")
DENSE = manifest.load_by_path("kernels", "dense")

# event names as the v5e trace of PR 24 gave them (shortened operand lists)
FILL_TEXT = ('%branch_1_fun.3 = (f32[2368,384,96]{2,1,0:T(8,128)}, '
             'f32[2368,384,1]{2,1,0:T(8,128)}) custom-call('
             'f32[384,96]{1,0:T(8,128)S(1)} %copy-done.276, s32[384,1]{1,0:T(8,128)S(1)} '
             '%copy-done.275, f32[2368,384,1]{2,1,0:T(8,128)} %and_convert_fusion, '
             'f32[2368,384,96]{2,1,0:T(8,128)} %a, f32[2368,384,96]{2,1,0:T(8,128)} %b, '
             'f32[2368,384,96]{2,1,0:T(8,128)} %c), custom_call_target="tpu_custom_call", '
             'operand_layout_constraints={f32[384,96]{1,0}, s32[384,1]{1,0}}')
DENSE_TEXT = ('%dense_interior_scores_batch.12 = f32[384,2304,9]{2,1,0:T(8,128)} custom-call('
              'f32[384,9,272,96]{3,2,1,0:T(8,128)} %a, f32[384,9,272,96]{3,2,1,0:T(8,128)} %b, '
              'f32[384,9,272,96]{3,2,1,0:T(8,128)} %c, f32[384,9,272,96]{3,2,1,0:T(8,128)} %d, '
              'f32[384,9,272,8]{3,2,1,0:T(8,128)} %e, f32[384,9,272,72]{3,2,1,0:T(8,128)} %f, '
              's32[384,1,1]{2,1,0:T(1,128)S(1)} %g, s32[384,9,4,1]{3,2,1,0:T(4,128)S(1)} %h), '
              'custom_call_target="tpu_custom_call", '
              'operand_layout_constraints={f32[384,9,272,96]{3,2,1,0}}')


@pytest.mark.parametrize("nc,reads,width,ops,nbytes", [
    # 256 reads x 2112 columns x W96: 27 operations a cell, 4 tensors of 207.6 MB
    (2112, 256, 96, 27 * 2112 * 256 * 96,
     4 * (4 * 2112 * 256 * 96 + 2 * 2112 * 256 + 256 * 96 + 256)),
    # the 576-column bucket runs W64: 24 operations a cell
    (576, 1024, 64, 24 * 576 * 1024 * 64,
     4 * (4 * 576 * 1024 * 64 + 2 * 576 * 1024 + 1024 * 64 + 1024)),
])
def test_fill_counts(nc, reads, width, ops, nbytes):
    assert FILL.work_from_dims(nc, reads, width) == (ops, nbytes)


def test_fill_least_time_is_hbm_bound_at_2kb():
    ops, nbytes = FILL.work_from_dims(2112, 256, 96)
    assert nbytes / 819e9 == pytest.approx(1.02e-3, rel=0.01)      # about 1 ms of bytes
    assert ops / 197e12 < nbytes / 819e9


def test_calls_are_read_from_the_trace_text():
    import re
    assert re.search(FILL.MATCH, FILL_TEXT) and not re.search(FILL.MATCH, DENSE_TEXT)
    assert re.search(DENSE.MATCH, DENSE_TEXT) and not re.search(DENSE.MATCH, FILL_TEXT)
    call = roofline.parse_call(FILL_TEXT)
    assert call["outputs"] == [("f32", (2368, 384, 96)), ("f32", (2368, 384, 1))]
    assert len(call["operands"]) == 6
    assert FILL.work(call) == FILL.work_from_dims(2368, 384, 96)
    call = roofline.parse_call(DENSE_TEXT)
    assert len(call["operands"]) == 8 and call["outputs"] == [("f32", (384, 2304, 9))]
    ops, nbytes = DENSE.work(call)
    assert ops == 384 * 2304 * 9 * 4 * 96
    assert nbytes == 4 * (4 * 384 * 9 * 272 * 96 + 384 * 9 * 272 * (8 + 72) + 384 + 384 * 36
                          + 384 * 2304 * 9)
    assert roofline.parse_call("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") is None


@pytest.mark.parametrize("jmax,width", [(576, 64), (2304, 96)])
def test_dense_counts_scale_with_positions_and_band(jmax, width):
    ops, nbytes = DENSE.work_from_dims(32 * 12, jmax, width, 1.0)
    assert ops == 384 * jmax * 9 * 4 * width and nbytes == 1.0
