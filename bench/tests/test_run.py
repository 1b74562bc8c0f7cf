"""``bench/run.py`` from the command line: no chip, no result line."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "allpairs-160k.solve", "--seed", str(2**33 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("PYTHONPATH", None)
    e.update(env or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"result line printed: {line}")


def test_no_tpu_exits_nonzero_without_a_result_line():
    p = _run(ROOT)
    assert p.returncode != 0
    _no_result(p)
    assert "no TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program to
    run: the run fails before any result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    _no_result(p)


def test_every_entry_of_the_benchmark_has_its_files():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in spec["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert os.path.exists(os.path.join(ROOT, "bench", "systems",
                                           cfg["system"] + ".py"))
    for w in spec["workloads"]:
        mix = os.path.join(ROOT, "bench", "mixes", w["traffic"] + ".json")
        loop = json.load(open(mix))["loop"]
        assert os.path.exists(os.path.join(ROOT, "bench", "loops",
                                           loop + ".py")), loop
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]
