"""The trace reduction on hand-made traces."""
import pytest

from chipbench.harness import trace


def test_reduce_by_hand():
    ev = {"devices": {"/device:TPU:0": [
        ["%fusion.1", 0, 10], ["%fusion.2", 5, 10],     # compute 0..15
        ["%all-reduce.1", 20, 12],                      # collective 20..32
        ["%fusion.3", 25, 10],                          # compute 25..35
        ["%fusion.4", 45, 5],                           # compute 45..50
    ]}, "modules": {"/device:TPU:0": [
        ["jit_step", 0, 35], ["jit_eval", 45, 5]]},
        "host": [["chipbench:step", 10, 20], ["chipbench:eval", 18, 4]]}
    r = trace.reduce(ev, 0, 50)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(35e-9)      # 0..15, 20..35, 45..50
    assert r["compute_s"] == pytest.approx(30e-9)   # 0..15, 25..35, 45..50
    assert r["collective_s"] == pytest.approx(12e-9)
    assert r["collective_alone_s"] == pytest.approx(5e-9)  # 20..25
    # compute gaps 15..25 (middle 20: eval, the innermost span) and
    # 35..45 (no span: host)
    assert r["idle_gaps"] == [["eval", pytest.approx(10e-9)],
                              ["host", pytest.approx(10e-9)]]
    assert r["device_ops"][0] == ["%all-reduce.1", pytest.approx(12e-9)]
    assert (r["top_module"], r["top_module_runs"]) == ("jit_step", 1.0)


def test_window_ends_where_a_device_trace_ends():
    ev = {"devices": {"/device:TPU:0": [["%fusion", 0, 60]]}, "host": []}
    r = trace.reduce(ev, 0, 100)
    assert r["window_s"] == pytest.approx(60e-9)
    assert r["busy_s"] == pytest.approx(60e-9)


def test_reduce_clips_to_window_and_averages_devices():
    ev = {"devices": {"/device:TPU:0": [["fusion", 0, 100]],
                      "/device:TPU:1": [["fusion", 50, 100]]},
          "host": []}
    r = trace.reduce(ev, 40, 80)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((40e-9 + 30e-9) / 2)
    assert r["collective_s"] == 0.0
