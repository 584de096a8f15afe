import json
import os

import pytest

from cellbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def planes():
    # one device: two programs; ops overlap and nest; two idle gaps
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_body(123)", 0.0, 1.0),
                            ("jit_fn(77)", 2.0, 0.5),
                            ("jit_body(123)", 3.0, 1.2)],
            "XLA Ops": [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0.0, 0.6),
                        ("%fusion.2 = f32[8] fusion(f32[8] %p)", 0.5, 0.5),
                        ("%copy.9 = f32[8] copy(f32[8] %x)", 2.0, 0.5),
                        ("%while.3 = f32[8] while(f32[8] %x)", 3.0, 1.2),
                        ("%fusion.7 = f32[8] fusion(f32[8] %p)", 3.1, 0.2)],
        },
        "/host:CPU": {
            "python": [("cellbench.step", 0.9, 1.2),
                       ("cellbench.wait_for_arrival", 2.4, 0.7)],
        },
    }


def test_busy_is_the_union_not_the_sum():
    r = tr.reduce_planes(planes())
    # [0,1.0] u [2.0,2.5] u [3.0,4.2] = 1.0 + 0.5 + 1.2
    assert r.busy_s == pytest.approx(2.7)
    assert r.devices == 1


def test_program_durations_by_name_without_the_fingerprint():
    r = tr.reduce_planes(planes())
    assert r.modules["jit_body"] == pytest.approx([1.0, 1.2])
    assert r.modules["jit_fn"] == pytest.approx([0.5])


def test_top_operations_share_a_name_across_instances():
    r = tr.reduce_planes(planes())
    top = dict(r.top_ops)
    assert top["fusion fusion"] == pytest.approx(0.6 + 0.5 + 0.2)
    assert r.top_ops[0][0] == "fusion fusion"
    assert top["while while"] == pytest.approx(1.2)


def test_the_whole_table_is_kept_and_ten_rows_are_printed():
    p = planes()
    p["/device:TPU:0"]["XLA Ops"] += [
        (f"%op_{'abcdefghijkl'[k]}.1 = f32[8] add(f32[8] %x)",
         5.0 + k, 0.001 * (k + 1)) for k in range(12)]
    r = tr.reduce_planes(p)
    assert len(r.per_op) == 15 and len(r.top_ops) == 10
    assert r.per_op["op_a add"] == pytest.approx(0.001)       # the least
    assert "op_a add" not in dict(r.top_ops)
    assert dict(r.top_ops) == {n: r.per_op[n] for n, _ in r.top_ops}
    assert tr.count_events(p) == 3 + 5 + 12 + 2


def test_idle_gaps_go_to_the_host_span_that_holds_them():
    r = tr.reduce_planes(planes())
    gaps = dict(r.idle_gaps)
    # gap [1.0, 2.0] sits in cellbench.step (0.9-2.1), gap [2.5, 3.0]
    # in cellbench.wait_for_arrival (2.4-3.1)
    assert gaps["cellbench.step"] == pytest.approx(1.0)
    assert gaps["cellbench.wait_for_arrival"] == pytest.approx(0.5)


def test_several_devices_average_and_the_busiest_is_read():
    p = planes()
    p["/device:TPU:1"] = {"XLA Modules": [("jit_body(1)", 0.0, 0.1)],
                          "XLA Ops": [("%a.1 = f32[1] add(f32[1] %x)",
                                       0.0, 0.1)]}
    r = tr.reduce_planes(p)
    assert r.devices == 2
    assert r.busy_s == pytest.approx((2.7 + 0.1) / 2)
    assert len(r.modules["jit_body"]) == 2          # device 0's


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes({"/host:CPU": {}})


def sweep_busy(ops):
    """Busy time by an endpoint sweep: another algorithm than the
    reduction's interval merge."""
    pts = sorted([(s, 1) for _, s, d in ops]
                 + [(s + d, -1) for _, s, d in ops])
    depth, busy, last = 0, 0.0, None
    for t, k in pts:
        if depth > 0:
            busy += t - last
        depth, last = depth + k, t
    return busy


def test_recorded_chip_trace():
    """Planes recorded on the v5e by this benchmark (`--keep-planes`,
    cell qwen3-8b-1c.chat-steady, seed 301, PR 23), each line cut to
    its first 400 events.  Read off the file by hand: 179 decode steps
    of 106.83-106.84 ms, 8 prefills (9.7 to 63 ms) and 8 inserts."""
    with open(os.path.join(HERE, "data",
                           "chat-steady.planes.json")) as f:
        rec = json.load(f)
    planes_ = {pn: {ln: [tuple(e) for e in evs]
                    for ln, evs in lines.items()}
               for pn, lines in rec["planes"].items()}
    r = tr.reduce_planes(planes_)
    assert r.devices == 1
    ops = planes_["/device:TPU:0"]["XLA Ops"]
    assert r.busy_s == pytest.approx(sweep_busy(ops), rel=1e-9)
    assert r.busy_s == pytest.approx(0.0613176, rel=1e-5)
    assert len(r.modules["jit_body"]) == 179
    assert len(r.modules["jit_fn"]) == 8 == len(r.modules["jit_insert"])
    body = sorted(r.modules["jit_body"])
    assert body[len(body) // 2] == pytest.approx(0.10683, rel=1e-3)
    assert max(r.modules["jit_fn"]) == pytest.approx(0.0630, rel=1e-2)
    assert {n for n, _ in r.idle_gaps} <= {
        "cellbench.step", "cellbench.submit",
        "cellbench.wait_for_arrival", "host:unannotated"}
