"""bench.py's driver loop: every section runs in this one process, and
a failed section fails the whole run once the rest have run."""

import importlib.util
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "measure_transport", lambda: {})
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    return mod


@pytest.mark.parametrize("fails", [[], ["a"], ["a", "c"]])
def test_failed_section_fails_the_run_after_the_rest(bench, monkeypatch,
                                                     fails):
    ran = []

    def section(name):
        def run(args):
            ran.append(name)
            if name in fails:
                raise RuntimeError(name)
        return run

    monkeypatch.setattr(bench, "BENCHES",
                        {n: section(n) for n in ("a", "b", "c")})
    if fails:
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code and all(n in str(e.value.code) for n in fails)
    else:
        bench.main()
    assert ran == ["a", "b", "c"]
