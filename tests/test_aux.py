"""Aux subsystems: stats registry/thread."""

import time

import pytest

from uccl_tpu.utils import stats


class TestStats:
    def test_registry_snapshot(self):
        reg = stats.StatsRegistry()
        reg.register("engine", lambda: {"tx": 10.0, "rx": 5.0})
        reg.register("broken", lambda: 1 / 0)
        snap = reg.snapshot()
        assert snap["engine"] == {"tx": 10.0, "rx": 5.0}
        assert "error" in snap["broken"]
        reg.unregister("engine")
        assert "engine" not in reg.snapshot()

    def test_thread_lifecycle(self):
        reg = stats.StatsRegistry()
        calls = []
        reg.register("c", lambda: calls.append(1) or {"n": len(calls)})
        stats._interval.set(0.05)
        try:
            t = stats.StatsThread(reg)
            t.start()
            t.start()  # idempotent
            time.sleep(0.3)
            t.stop()
        finally:
            stats._interval.reset()
        assert len(calls) >= 2

    def test_quiet(self):
        reg = stats.StatsRegistry()
        calls = []
        reg.register("c", lambda: calls.append(1) or {})
        stats._quiet.set(True)
        stats._interval.set(0.05)
        try:
            t = stats.StatsThread(reg)
            t.start()
            time.sleep(0.2)
            t.stop()
        finally:
            stats._quiet.reset()
            stats._interval.reset()
        assert calls == []

