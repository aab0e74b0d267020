"""A configuration, a traffic mix and a per-layer metric are added as new
files only; a run that finds no TPU fails; the configuration files keep
the program's registered widths except what they list as reduced."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

from bench.harness import spec
from bench.harness.serve import program_config

from bench_tiny import BENCH, ROOT, make_root


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_pieces_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(root)
    b = os.path.join(root, "bench")
    # a new configuration, traffic mix and metric: new files ...
    with open(os.path.join(b, "configs", "granite-8b-L12.json"), "w") as f:
        cfg = json.load(open(os.path.join(b, "configs",
                                          "granite-8b-L9.json")))
        json.dump(dict(cfg, name="granite-8b-L12", num_hidden_layers=12), f)
    with open(os.path.join(b, "traffic", "burst-8.json"), "w") as f:
        mix = json.load(open(os.path.join(b, "traffic", "chat-8.json")))
        json.dump(dict(mix, name="burst-8"), f)
    with open(os.path.join(b, "metrics", "ticks_per_s.py"), "w") as f:
        f.write("def read(run):\n    return run.ticks / run.window_s\n")
    # ... and new entries in BENCHMARK.json
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        s = json.load(f)
    s["configs"].append({"name": "granite-8b-L12", "source": "x",
                         "file": "bench/configs/granite-8b-L12.json",
                         "reduced": ["num_hidden_layers"], "why": "x"})
    s["workloads"].append({"name": "granite-burst", "chips": 1,
                           "config": "granite-8b-L12", "traffic": "burst-8",
                           "why": "x"})
    s["per_layer"].append({"name": "ticks_per_s", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "router and scheduler",
                           "moves": "itl_p50_ms",
                           "workloads": ["granite-burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(s, f)
    cell = spec.load_cell(root, "granite-burst", bench_root=b)
    assert cell.config["num_hidden_layers"] == 12
    assert cell.traffic["name"] == "burst-8"
    assert "ticks_per_s" in [m["name"] for m in cell.per_layer]

    class FakeRun:
        ticks, window_s = 30, 1.5
    got = spec.read_metrics([m for m in cell.per_layer
                             if m["name"] == "ticks_per_s"], FakeRun(),
                            bench_root=b)
    assert got == {"ticks_per_s": {"value": 20.0, "unit": "1/s"}}
    after = _digests(root)
    changed = [k for k in before if before[k] != after.get(k)]
    assert changed == ["BENCHMARK.json"]
    assert sorted(set(after) - set(before)) == sorted([
        os.path.join("bench", "configs", "granite-8b-L12.json"),
        os.path.join("bench", "traffic", "burst-8.json"),
        os.path.join("bench", "metrics", "ticks_per_s.py")])


def test_a_reader_that_finds_nothing_leaves_the_metric_out(tmp_path):
    root = make_root(tmp_path)
    cell = spec.load_cell(root, "tiny", bench_root=os.path.join(root,
                                                                "bench"))

    class NoTrace:
        trace = None
        peaks = {}
    got = spec.read_metrics([m for m in cell.per_layer
                             if m["name"] in ("decode_step_ms",
                                              "decode_roofline",
                                              "device_idle")],
                            NoTrace(), bench_root=os.path.join(root, "bench"))
    assert got == {}


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-chat",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_without_a_result():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_bench_files_alone_fail_without_a_result(tmp_path):
    shutil.copytree(BENCH, os.path.join(tmp_path, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), {})
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_configs_keep_the_registered_widths():
    from repro.configs import get_config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        s = json.load(f)
    for c in s["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            model = json.load(f)
        assert model["reduced"] == c["reduced"]
        got, want = program_config(model), get_config(model["arch"])
        for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                      "vocab_size", "ffn_act", "ffn_gated", "tie_embeddings",
                      "rope_theta", "norm_eps"):
            assert getattr(got, field) == getattr(want, field), field
        assert model["published"]["num_hidden_layers"] == want.n_layers
        assert set(model["published"]) == set(model["reduced"])


def test_every_listed_metric_has_a_reader():
    """Each metric of BENCHMARK.json resolves to a reader; a split name
    ``<base>.<variant>`` reads with ``<base>.py``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        s = json.load(f)
    for m in s["end_to_end"] + s["per_layer"]:
        assert callable(spec.reader(m["name"])), m["name"]
    assert spec.reader("device_idle.chat").__code__.co_filename.endswith(
        os.path.join("metrics", "device_idle.py"))
