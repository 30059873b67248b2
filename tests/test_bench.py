"""bench.py is one in-process run: no child, no fallback, no cache of
old results.  (The supervisor these tests replace is gone with the
remote chip it was built for.)"""

import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_cpu_runs_only_when_the_caller_asked_for_it(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.require_backend() == "cpu"


def test_no_tpu_and_no_request_for_cpu_fails(monkeypatch):
    # the test session's backend IS cpu; without JAX_PLATFORMS=cpu that
    # is "found no TPU", and the run must end non-zero
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.require_backend()
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)


def test_every_device_config_checks_the_backend_first():
    import inspect

    for n, fn in bench.CONFIGS.items():
        src = inspect.getsource(fn)
        if n == 5:      # media + labels: host only, never touches JAX
            assert "jax" not in src
        else:
            assert "require_backend()" in src, n


def test_starts_no_child_and_keeps_no_knobs():
    with open(bench.__file__) as f:
        src = f.read()
    assert not re.search(r"\b(subprocess|Popen|os\.fork|multiprocessing)\b",
                         src)
    assert "SW_" + "BENCH" not in src
    assert not os.path.exists(os.path.join(
        os.path.dirname(bench.__file__), "BENCH_TPU_CACHE.json"))


def test_main_runs_exactly_the_config_asked_for(monkeypatch):
    ran = []
    monkeypatch.setattr(sys, "argv", ["bench.py", "--config", "3"])
    monkeypatch.setattr(bench, "CONFIGS",
                        {n: (lambda n=n: ran.append(n)) for n in range(1, 7)})
    bench.main()
    assert ran == [3]


def test_a_failing_config_fails_the_run(monkeypatch):
    def boom():
        raise RuntimeError("phase failed")

    monkeypatch.setattr(sys, "argv", ["bench.py", "--config", "2"])
    monkeypatch.setattr(bench, "CONFIGS", {n: boom for n in range(1, 7)})
    with pytest.raises(RuntimeError):
        bench.main()


def test_reduced_cpu_run_says_backend_cpu(monkeypatch, capsys):
    import json

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench.bench_analytics()
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["backend"] == "cpu"
    assert doc["metric"] == "analytics_events_per_sec_per_chip"
