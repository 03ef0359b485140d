"""A configuration, a traffic mix and a metric are added by adding files
and ``BENCHMARK.json`` entries, without editing any file of the benchmark."""
import json
import shutil

from bench.test_bench_checks import BENCH, ROOT, drive

METRIC = '''"""Panels answered in the run (a throwaway metric for the test)."""


def read(record, trace):
    return sum("answers" in p for p in record["panels"])
'''


def test_new_config_mix_and_metric_by_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())

    cfg = json.loads((BENCH / "configs" / "tpch-lineitem-1chip.json")
                     .read_text())
    cfg["name"] = "tiny-lineitem"
    (root / "bench" / "configs" / "tiny-lineitem.json").write_text(
        json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "dash.json").read_text())
    mix["name"] = "q6-only"
    mix["templates"] = [dict(mix["templates"][0], share=1.0)]
    mix["slots_warm"] = {"scalar": 4}
    mix["rate_per_s"] = 4
    (root / "bench" / "traffic" / "q6-only.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "panels_answered.py").write_text(METRIC)

    bm["configs"].append({"name": "tiny-lineitem", "source": "test",
                          "file": "bench/configs/tiny-lineitem.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-q6", "config": "tiny-lineitem",
                            "traffic": "q6-only", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "panels_answered", "unit": "panels",
                            "better": "higher", "source": "program_counter",
                            "layer": "service", "moves": "tte_p50_s",
                            "workloads": ["tiny-q6"]})
    for m in bm["end_to_end"]:
        if m["name"].startswith("tte_"):
            m["workloads"].append("tiny-q6")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    res = drive(tmp_path, {"root": str(root), "cell": "tiny-q6",
                           "rows": 1 << 20, "seconds": 3, "seed": 5,
                           "trace": 1})
    assert res["correct"]
    assert res["metrics"]["panels_answered"]["value"] == res["attempted"] > 0
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*")
             if p.is_file() and p.relative_to(root) in before}
    assert after == before
