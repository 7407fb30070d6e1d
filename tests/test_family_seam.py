"""The seam inside ``ray_tpu/models/``: a family's file holds a family.

A mechanism that families share has ONE owner, under ``ray_tpu/ops/`` or
beside ``PagedFamily`` in ``models/generate.py``, and the arrows point one
way: family -> ``generate.py`` -> ``ops/``. Held here without building a
model: (1) no family module imports another family module; (2) nothing under
``ops/`` imports ``models`` or ``serve``; (3) the pool's cell rule
(``generate.prefill_cells`` / ``decode_cells``) against hand-worked tables;
(4) the capacity clip and the body that calls ``held_experts_ffn`` are
written in one module each, and every expert family's ``expert_layer`` is a
binding onto ``moe.expert_layer`` looked up when it is called.
"""

import ast
import glob
import importlib
import os
import re

import numpy as np
import pytest

from ray_tpu.models import generate
from ray_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = sorted(glob.glob(os.path.join(ROOT, "ray_tpu", "models", "*.py")))
OPS = sorted(glob.glob(os.path.join(ROOT, "ray_tpu", "ops", "*.py")))
# What every family stands on; GPT-2 (``transformer``) is the family that
# ``generate`` itself serves, so it is below the seam, not beside it.
SHARED = {"__init__", "generate", "transformer", "training", "mlp"}
_name = lambda path: os.path.splitext(os.path.basename(path))[0]  # noqa: E731
FAMILIES = [p for p in MODELS if _name(p) not in SHARED]
EXPERT_FAMILIES = ("longcat", "kimi_k2", "afmoe", "nemotron_h", "mimo_v2",
                   "glm_dsa", "lfm2")


def imported_modules(path):
    """Every module ``path`` imports, absolute: ``from a.b import c`` gives
    ``a.b`` and ``a.b.c`` (``c`` may be a module), a relative import is
    resolved against the file's package."""
    package = os.path.relpath(os.path.dirname(path), ROOT).replace(os.sep, ".")
    out = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package.split(".")
                up = up[:len(up) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            out.add(base)
            out.update(f"{base}.{a.name}" for a in node.names)
    return out


def test_the_guard_sees_the_nine_families():
    assert {_name(p) for p in FAMILIES} == {
        "afmoe", "falcon_h1", "glm_dsa", "kimi_k2", "lfm2", "longcat",
        "mimo_v2", "nemotron_h", "olmo_hybrid"}
    assert set(EXPERT_FAMILIES) <= {_name(p) for p in FAMILIES}


@pytest.mark.parametrize("path", FAMILIES, ids=_name)
def test_no_family_imports_a_sibling(path):
    """Of ``ray_tpu.models`` a family reads ``generate`` alone (and through
    it nothing of another family's)."""
    from_models = {m for m in imported_modules(path)
                   if m.startswith("ray_tpu.models.")}
    siblings = {m for m in from_models
                if m.split(".")[2] in map(_name, FAMILIES)}
    assert not siblings, f"{_name(path)} reaches into {sorted(siblings)}"
    assert {m.split(".")[2] for m in from_models} <= {"generate"}


@pytest.mark.parametrize("path", OPS, ids=_name)
def test_ops_stand_below_models_and_serve(path):
    above = {m for m in imported_modules(path)
             if m.startswith(("ray_tpu.models", "ray_tpu.serve"))}
    assert not above, f"ops/{_name(path)} imports {sorted(above)}"


def test_generate_imports_no_family():
    reached = {m.split(".")[2] for m in imported_modules(
        os.path.join(ROOT, "ray_tpu", "models", "generate.py"))
        if m.startswith("ray_tpu.models.")}
    assert reached <= {"transformer"}


# -- (3) the cell rule, by hand ------------------------------------------------
# Blocks of 4 rows. A prefill of a 12-row bucket at positions 2..13 through a
# table of three blocks (capacity 12), 7 rows real.
PREFILL = dict(table=[5, 9, 3], start_pos=2, suffix_len=7, P=12, bt=4)


@pytest.mark.parametrize("row, want", [
    (0, (2, True, 5, 2)),       # the first real row: block table[0], row 2
    (2, (4, True, 9, 0)),       # a row of the second block
    (6, (8, True, 3, 0)),       # the last real row, in the third
    (7, (9, False, 0, 1)),      # a pad row inside the table: trash block 0
    (10, (12, False, 0, 0)),    # a pad row AT the table's capacity
    (11, (13, False, 0, 1)),    # and one past it: the index is clipped
], ids=["first", "second_block", "last_real", "pad", "pad_at_capacity",
        "pad_past_capacity"])
def test_a_prefills_cells(row, want):
    p = PREFILL
    positions, valid, blk, off = generate.prefill_cells(
        np.asarray(p["table"], np.int32), p["start_pos"], p["suffix_len"],
        p["P"], p["bt"])
    got = (int(positions[row]), bool(valid[row]), int(blk[row]), int(off[row]))
    assert got == want
    assert positions.shape == valid.shape == blk.shape == off.shape == (12,)


def test_a_prefills_trash_block_takes_the_pad_rows_alone():
    p = PREFILL
    _, valid, blk, _ = generate.prefill_cells(
        np.asarray(p["table"], np.int32), p["start_pos"], p["suffix_len"],
        p["P"], p["bt"])
    assert np.array_equal(np.asarray(blk) == 0, ~np.asarray(valid))


# A decode step of two rows a slot through tables of two blocks (capacity 8):
# a slot inside its table, one that crosses into its last cell and over the
# capacity, one at and past capacity, a parked slot (an all-trash table).
DECODE = dict(tables=[[5, 9], [7, 2], [4, 6], [0, 0]], lengths=[3, 7, 8, 6],
              T=2, bt=4)


@pytest.mark.parametrize("slot, t, want", [
    (0, 0, (3, 5, 3)),          # the last row of the first block
    (0, 1, (4, 9, 0)),          # a row of the second block
    (1, 0, (7, 2, 3)),          # the table's last cell: still written
    (1, 1, (8, 0, 3)),          # AT capacity: trash, never the last cell's block
    (2, 1, (9, 0, 3)),          # past capacity: trash
    (3, 0, (6, 0, 2)),          # a parked slot's row: its table is trash
], ids=["in_block", "second_block", "last_cell", "at_capacity",
        "past_capacity", "parked"])
def test_a_decode_steps_cells(slot, t, want):
    d = DECODE
    positions, blk, off = generate.decode_cells(
        np.asarray(d["tables"], np.int32), np.asarray(d["lengths"], np.int32),
        d["T"], d["bt"])
    assert (int(positions[slot, t]), int(blk[slot, t]),
            int(off[slot, t])) == want
    assert positions.shape == blk.shape == off.shape == (4, 2)


def test_a_decode_steps_trash_block_takes_overhang_and_parked_rows_alone():
    d = DECODE
    _, blk, _ = generate.decode_cells(
        np.asarray(d["tables"], np.int32), np.asarray(d["lengths"], np.int32),
        d["T"], d["bt"])
    assert (np.asarray(blk) == 0).tolist() == [
        [False, False], [False, True], [True, True], [True, True]]


# -- (4) written once ----------------------------------------------------------

def _modules_where(found):
    return sorted(os.path.relpath(p, ROOT) for p in MODELS + OPS
                  if found(open(p).read()))


def test_the_capacity_clip_is_written_in_one_module():
    clip = re.compile(r"\bpos_c\b|clip\(positions //")
    assert _modules_where(clip.search) == ["ray_tpu/models/generate.py"]


def test_the_experts_product_is_called_from_one_module():
    def calls(source):
        return any(isinstance(n, ast.Call) and "held_experts_ffn" in (
            getattr(n.func, "id", None), getattr(n.func, "attr", None))
            for n in ast.walk(ast.parse(source)))
    assert _modules_where(calls) == ["ray_tpu/ops/moe.py"]


@pytest.mark.parametrize("name", EXPERT_FAMILIES)
def test_an_expert_familys_layer_is_a_binding_onto_the_one_body(name,
                                                                monkeypatch):
    """``family.expert_layer(lp, x, valid, c)`` hands ``moe.expert_layer``,
    looked up when it is called (so that a name assigned in ``ops/moe.py``
    reaches every family's program), what its config names; a shared expert
    runs through the family's own feed-forward, looked up at ITS call."""
    family = importlib.import_module(f"ray_tpu.models.{name}")
    seen = {}

    def body(lp, x, valid, **kw):
        seen.update(kw, args=(lp, x, valid))
        return "out", "counts"
    monkeypatch.setattr(moe, "expert_layer", body)
    c = family.tiny()
    assert family.expert_layer("lp", "x", "valid", c) == ("out", "counts")
    assert seen["args"] == ("lp", "x", "valid") and seen["held"] == c.held
    assert set(seen) - {"args", "shared", "form", "w_in"} == {
        "topk", "scale", "score", "renormalise", "held", "n_routed"}
    assert seen.get("form", "silu_gate") in moe.EXPERT_FORMS
    if "shared" in seen:
        ffn = "relu2_ffn" if name == "nemotron_h" else "gated_ffn"
        monkeypatch.setattr(family, ffn,
                            lambda fp, rows, dtype: ("shared", fp, rows))
        assert seen["shared"]("fp", "rows") == ("shared", "fp", "rows")
    assert ("shared" in seen) == (name in ("kimi_k2", "afmoe", "nemotron_h",
                                           "glm_dsa"))
