"""``tools/tick_hlo_copies.py``'s parser on a canned compiled module: the
shapes of a TPU compile's text (computation heads with ``/*index=5*/``
comments, tiled layouts, fusions that call computations, a loop body), cut
down by hand from the Brumby cell's decode tick at the parent of PR 42
(``tests/tick_hlo_canned.txt``: a compiled module's lines are long)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import tick_hlo_copies as thc  # noqa: E402

MIB = 1 << 20
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "tick_hlo_canned.txt")) as _f:
    HLO = _f.read()

LEAVES = {
    "embedding/word_embeddings": ("bf16", (151936, 5120)),
    "layers/attention/dense/kernel": ("bf16", (4, 5120, 5120)),
    "layers/mlp/fc1/kernel": ("bf16", (4, 5120, 2, 17408)),
    "layers/mlp/fc2/kernel": ("bf16", (4, 17408, 5120)),
    "layers/post_norm/scale": ("f32", (4, 5120)),
}


@pytest.fixture(scope="module")
def comps():
    return thc.parse_hlo(HLO)


def test_computations_and_instructions(comps):
    assert set(comps) == {
        "fused_computation.107.clone.clone", "fused_computation.85.clone.clone",
        "fused_computation.50.clone.clone", "fused_computation.9",
        "wide.region_1.19.clone.sunk", "main.56"}
    body = {i.name: i for i in comps["wide.region_1.19.clone.sunk"]}
    copy = body["copy.108"]
    assert copy.opcode == "copy"
    assert copy.operands == ("constant_dynamic-slice_fusion.8",)
    assert copy.shape == "bf16[1,5120,2,17408]{1,3,2,0:T(8,128)(2,1)}"
    assert copy.nbytes == 340 * MIB
    assert copy.op_name.endswith("while/body/dynamic_slice")
    fusion = body["constant_dynamic-slice_fusion.8"]
    assert fusion.opcode == "fusion"
    assert fusion.calls == "fused_computation.107.clone.clone"
    assert fusion.operands == ("get-tuple-element.780",
                               "get-tuple-element.741")
    assert body["wide.arg_tuple.0"].nbytes == 0          # a tuple
    assert thc.shape_bytes("s32[]{:T(128)}") == 4
    assert comps["main.56"][2].calls == "wide.region_1.19.clone.sunk"


@pytest.mark.parametrize("name,kind,leaf,reads,read_by", [
    ("constant_dynamic-slice_fusion.8", "fusion(dynamic-slice)",
     "layers/mlp/fc1/kernel", "{3,2,1,0:T(2,128)(2,1)}", "copy.108 copy"),
    ("copy.108", "copy", "layers/mlp/fc1/kernel",
     "{3,2,1,0:T(2,128)(2,1)}", "fusion.192 fusion[kOutput]"),
    ("copy.48", "copy", "embedding/word_embeddings",
     "{1,0:T(8,128)(2,1)}", None),
])
def test_every_moving_operation_over_a_mib(comps, name, kind, leaf, reads,
                                           read_by):
    rows = {r.instr.name: r for r in thc.moved(comps, MIB)}
    # the slice fused INTO the fc2 dot (fusion.181) reads the stack in
    # place and is no row; nor are the small copy, the fusion that adds,
    # the fusions around a convolution and the Pallas call
    assert set(rows) == {"constant_dynamic-slice_fusion.8", "copy.108",
                         "copy.48"}
    row = rows[name]
    assert row.kind == kind
    assert row.sources[0][1].endswith(reads)
    assert (row.consumers == ((read_by,) if read_by else ()))
    assert thc.leaf_of(LEAVES, row.instr.shape) == leaf


def test_report_names_leaf_layouts_and_consumer(comps):
    text = "\n".join(thc.report(HLO, LEAVES, MIB))
    assert text.startswith("3 moving operations of at least 1 MiB")
    assert ("copy.108 [copy] in wide.region_1.19.clone.sunk\n"
            "        340.0 MiB  leaf: layers/mlp/fc1/kernel\n") in text
    assert "writes  bf16[1,5120,2,17408]{1,3,2,0:T(8,128)(2,1)}" in text
    assert "read by fusion.192 fusion[kOutput]" in text
    # a lower floor lets the small copy in, and nothing that computes
    small = {r.instr.name: r for r in thc.moved(comps, 1 << 10)}
    assert set(small) == {"constant_dynamic-slice_fusion.8", "copy.108",
                          "copy.48", "copy.3"}
    # a leaf is named by dtype and dims, a reshaped layer of a stack by
    # its size; an activation that merely has a leaf's size is not one
    assert thc.leaf_of(LEAVES, small["copy.3"].instr.shape) == "-"
    assert thc.leaf_of(LEAVES, "bf16[34816,5120]{1,0}") == (
        "layers/mlp/fc1/kernel (by size)")


# ---------------------------------------------------------------------------
# the word-embedding table (PR 64): a lone ``take`` + tied head at the Falcon
# cell's shapes, compiled for a described v5e with the table as the device
# lays it out by default (``tick_hlo_table_cols.txt``: the parent's form) and
# in rows (``tick_hlo_table_rows.txt``), the index clamps cut by hand
# ---------------------------------------------------------------------------

TABLE = {"embedding/word_embeddings": ("bf16", (65024, 4544))}


def _canned(name):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           name)) as f:
        return f.read()


def test_a_table_copied_into_rows_for_the_gather_is_reported():
    text = _canned("tick_hlo_table_cols.txt")
    (row,) = thc.moved(thc.parse_hlo(text), 100 * MIB)
    assert (row.comp, row.instr.name, row.kind) == ("main.4", "copy", "copy")
    assert row.instr.nbytes == 65024 * 4544 * 2
    # the parameter lies vocabulary-minor, the gather wants rows
    assert row.sources == (("t.1", "bf16[65024,4544]{0,1:T(8,128)(2,1)}"),)
    assert row.instr.shape == "bf16[65024,4544]{1,0:T(8,128)(2,1)}"
    assert row.consumers == ("fusion fusion[kCustom]",)
    said = "\n".join(thc.report(text, TABLE, 100 * MIB))
    assert said.startswith("1 moving operations of at least 100 MiB, "
                           "563.6 MiB written in all")
    assert "563.6 MiB  leaf: embedding/word_embeddings\n" in said


def test_a_table_held_in_rows_is_read_as_it_lies():
    text = _canned("tick_hlo_table_rows.txt")
    comps = thc.parse_hlo(text)
    assert thc.moved(comps, 100 * MIB) == []
    assert thc.report(text, TABLE, 100 * MIB)[0].startswith(
        "0 moving operations of at least 100 MiB")
    entry = {i.name: i for i in comps["main.4"]}
    assert entry["t.1"].shape == "bf16[65024,4544]{1,0:T(8,128)(2,1)}"
    # the gather and the head's dot read the parameter; the dot through a
    # bitcast that moves nothing
    assert entry["fusion"].operands == ("t.1", "ids.1")
    assert entry["convolution_convert_fusion"].operands == ("copy-done",
                                                            "t.1")
    assert {i.opcode for i in comps["bitcast_fusion.1"]} == {"parameter",
                                                             "bitcast"}
    # the small copy left (the toy's output rows) is under any table's size
    assert [r.instr.name for r in thc.moved(comps, MIB)] == ["copy"]
