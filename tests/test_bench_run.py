"""benchmarks/run.py hides no failure: a bench that raises makes it exit
nonzero, after the other benches ran."""
import sys

import pytest


def test_raised_bench_exits_nonzero(monkeypatch, tmp_path):
    from benchmarks import run

    ran = []

    class Ok:
        @staticmethod
        def main(full=False):
            ran.append("ok")
            return {"row": 1}

    class Boom:
        @staticmethod
        def main(full=False):
            raise RuntimeError("bench failed")

    monkeypatch.setattr(run, "BENCHES", {"boom": Boom, "ok": Ok})
    monkeypatch.setattr(run, "enable_persistent_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "run", "--json-out", str(tmp_path / "out.json")])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "boom" in str(exc.value.code)
    assert ran == ["ok"]
