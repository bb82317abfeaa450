"""A new configuration, traffic mix and per-layer metric need only new
files and new entries: added to a temporary copy, the harness runs the
new cell and reports the new metric, with no file of the harness edited."""
import json

import bench_tiny_root as tiny


def test_new_config_mix_and_metric_by_files_only(tmp_path):
    root = tiny.make(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs/tiny_qwen2_5_3b.json").read_text())
    cfg["name"] = "tiny_other"
    (b / "configs/tiny_other.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic/serve_prompt.json").read_text())
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": 3.0}
    mix["output_len"]["median"] = 4
    (b / "traffic/serve_short.json").write_text(json.dumps(mix))
    (b / "limits/serve_short.tiny_other.json").write_text(
        json.dumps({"served_gap": tiny.LIMITS["served_gap"]}))
    (b / "metrics/ticks_admitting.py").write_text(
        "def read(rec):\n"
        "    t = rec['record'].get('ticks', [])\n"
        "    return float(sum(1 for k in t if k['admitted'])) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tiny_other",
                                 file="bench/configs/tiny_other.json"))
    bench["workloads"].append({
        "name": "serve_short.tiny_other", "config": "tiny_other",
        "traffic": "serve_short", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "ttft_p95_ms" == m["name"]:
            m["workloads"].append("serve_short.tiny_other")
    bench["per_layer"].append({
        "name": "ticks_admitting", "unit": "ticks", "better": "lower",
        "source": "host_clock", "layer": "serving engine",
        "moves": "ttft_p95_ms", "workloads": ["serve_short.tiny_other"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line, checks = tiny.run("serve_short.tiny_other", 2**31 + 1, 2.0, True,
                            root)
    assert line["correct"], checks
    assert line["attempted"] == 6
    assert line["metrics"]["ticks_admitting"]["value"] >= 1
    assert "admit_tick_ms" not in line["metrics"]
