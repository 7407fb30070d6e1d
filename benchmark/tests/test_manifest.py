"""BENCHMARK.json and the files it names; and that a later PR extends the
benchmark with new files and entries alone."""

import json
import os
import shutil

from benchmark.manifest import NAME_RE, UNIT_RE, Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONTRACT_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}


def test_shipped_manifest_is_sound():
    man = Manifest(ROOT)
    assert man.check() == []
    assert set(man.doc) == CONTRACT_KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= man.doc["run_seconds"] <= 51


def test_every_cell_resolves_and_every_moves_is_reported():
    man = Manifest(ROOT)
    for cell, w in man.cells.items():
        assert os.path.isfile(man.config_file(w["config"]))
        driver = man.load_traffic(w["traffic"])["driver"]
        assert os.path.isfile(os.path.join(man.home, "drivers", f"{driver}.py"))
        e2e = {m["name"] for m in man.metrics_of(cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in man.metrics_of(cell, "per_layer"):
            assert m["moves"] in e2e, (cell, m["name"])
            assert callable(man.reader(m["name"]))
    layers = {}
    for m in man.doc["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        layers.setdefault(m["layer"].split(":")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())     # one layer, one spelling


def test_names_units_and_entry_keys():
    doc = Manifest(ROOT).doc
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "bound" not in m
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME_RE.match(m["name"]) and UNIT_RE.match(m["unit"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    roof = [m for m in doc["per_layer"] if "roofline" in m["name"] or "mfu" in m["name"]]
    assert roof and all(m["unit"] == "%" for m in roof)


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A throw-away configuration, traffic mix and per-layer metric, added to
    a copy of the benchmark without editing one shipped file."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    home = tmp_path / "benchmark"

    cfg = json.loads((home / "configs" / "gpt2-medium.json").read_text())
    cfg.update(name="gpt2-large", n_layer=36, n_embd=1280, n_head=20, n_inner=5120,
               source="https://huggingface.co/openai-community/gpt2-large/blob/main/config.json")
    cfg["factory"]["path"] = "ray_tpu.models.transformer.gpt2_large"
    (home / "configs" / "gpt2-large.json").write_text(json.dumps(cfg))
    mix = json.loads((home / "traffic" / "decode-batch.json").read_text())
    mix.update(name="decode-long", output_tokens={"dist": "uniform", "lo": 256, "hi": 640})
    (home / "traffic" / "decode-long.json").write_text(json.dumps(mix))
    (home / "readers" / "throwaway.py").write_text(
        "def completions(run, spec):\n"
        "    return float(sum(1 for r in run['records'] if r.get('finish_reason')))\n")
    (home / "metrics" / "completions.long.json").write_text(json.dumps({
        "name": "completions.long", "unit": "count", "better": "higher",
        "source": "host_clock", "reader": "benchmark/readers/throwaway.py:completions"}))

    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "gpt2-large", "source": cfg["source"],
                           "file": "benchmark/configs/gpt2-large.json", "reduced": [], "why": "example"})
    cell = "gpt2-large.decode-long"
    doc["workloads"].append({"name": cell, "config": "gpt2-large", "traffic": "decode-long",
                             "chips": 1, "why": "example"})
    for m in doc["end_to_end"]:
        if m["name"] == "serve_out_tok_s":
            m["workloads"].append(cell)
    doc["per_layer"].append({"name": "completions.long", "unit": "count", "better": "higher",
                             "source": "host_clock", "layer": "load generator",
                             "moves": "serve_out_tok_s", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    man = Manifest(str(tmp_path))
    assert man.check() == []
    assert man.load_config("gpt2-large")["n_layer"] == 36
    assert man.load_traffic("decode-long")["output_tokens"]["hi"] == 640
    names = [m["name"] for m in man.metrics_of(cell, "per_layer")]
    assert names == ["completions.long"]
    run = {"records": [{"finish_reason": "stop"}, {"finish_reason": None}]}
    assert man.reader("completions.long")(run) == 1.0
    after = {p: p.read_bytes() for p in before}
    assert after == before                                # no shipped file was edited
