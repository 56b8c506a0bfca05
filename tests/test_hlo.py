"""HLO collective-bytes parser (``repro.analysis.hlo``), used by the SA dry run."""
from repro.analysis.hlo import collective_bytes


def test_hlo_parser_counts_known_ops():
    text = """
  %x = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[512,256]{1,0} all-gather(%x), replica_groups=[2,4]<=[8], dimensions={0}
  %rs = f32[32,256]{1,0} reduce-scatter(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = bf16[64]{0} collective-permute(%y), source_target_pairs={{0,1}}
  %aa = s32[16,4]{1,0} all-to-all(%z), replica_groups={{0,1}}
"""
    got = collective_bytes(text)
    assert got["all-reduce"] == 128 * 256 * 4
    assert got["all-gather"] == 512 * 256 * 4 // 4  # operand = out / group
    assert got["reduce-scatter"] == 32 * 256 * 4 * 4  # operand = out * group
    assert got["collective-permute"] == 64 * 2
    assert got["all-to-all"] == 16 * 4 * 4
    assert got["total"] == sum(
        v for k, v in got.items() if k != "total"
    )


def test_hlo_parser_ignores_done_of_async_pair():
    text = """
  %s = f32[8]{0} all-gather-start(%x), replica_groups={{0,1}}
  %d = f32[8]{0} all-gather-done(%s)
"""
    got = collective_bytes(text)
    assert got["all-gather"] == 8 * 4 // 2
