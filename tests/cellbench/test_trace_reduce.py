"""The reduction from a profiler trace to device numbers, on a recorded trace.

``data/folder_backlog_2calls.json`` is the first chip trace of
``orset_folder_1k.backlog`` (TPU v5 lite, PR 23): two timed ``compact()``
calls, flattened by ``trace_reduce.load_xplane`` and cut by ``sample`` to the
lines the reducer reads.  The expected numbers were worked out from the file
by hand (sums of its events), not by the code under test.
"""

import json
import os

import numpy as np
import pytest

from cellbench import kernel_bytes, trace_reduce as tr
from cellbench.readers import roofline_pct, trace_kernel_ms

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "folder_backlog_2calls.json")) as fh:
        return json.load(fh)


def test_window_is_the_summed_length_of_the_timed_calls(recorded):
    calls = [e for _, line in tr._lines(recorded, "/host:") for e in line["events"]
             if e[0] == tr.CALL]
    assert len(calls) == 2
    reduced = tr.reduce(recorded)
    assert reduced["window_s"] == pytest.approx(sum(e[2] for e in calls) / 1e9)
    assert reduced["window_s"] == pytest.approx(9.244027878)
    assert reduced["devices"] == 1


def test_busy_is_the_union_of_device_ops_inside_the_calls(recorded):
    reduced = tr.reduce(recorded)
    # the fold program is the only thing that ran: its module events bound
    # the union of its ops from above
    module_s, n = tr.kernel_seconds(recorded, "XLA Modules", ["_fold_ablk"])
    assert n == 2 and module_s == pytest.approx(0.001683902)
    assert 0 < reduced["busy_s"] <= module_s
    assert reduced["busy_s"] == pytest.approx(0.001683261)
    idle_share = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle_share == pytest.approx(0.99982, abs=1e-5)


def test_kernel_time_by_name(recorded):
    ops_s, n = tr.kernel_seconds(recorded, "XLA Ops", ["_fold_ablk"])
    assert n == 2 and ops_s == pytest.approx(0.001063247)
    assert tr.kernel_seconds(recorded, "XLA Ops", ["no_such_kernel"]) == (0.0, 0)
    top = tr.reduce(recorded)["device_ops"]
    assert top[0] == ["_fold_ablk.1", pytest.approx(0.001063247)]
    assert len(top) == 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_idle_gaps_go_to_the_innermost_open_span(recorded):
    reduced = tr.reduce(recorded)
    gaps = dict(reduced["idle_gaps"])
    # all idle time is charged, to someone
    full = tr.charge_gaps(
        tr.complement(
            tr.clip(tr.union(tr._intervals(
                [e for _, l in tr._lines(recorded, "/device:", tr.OPS_LINE)
                 for e in l["events"]])), tr.call_windows(recorded)),
            tr.call_windows(recorded)),
        [e for _, l in tr._lines(recorded, "/host:") for e in l["events"]
         if e[0] != tr.CALL])
    assert sum(full.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    # delta.verify runs on a worker thread for 1.77 s and 2.02 s: the device
    # is idle throughout, so it is charged in full
    assert gaps["delta.verify"] == pytest.approx(1.772367 + 2.024842, abs=1e-3)
    # compact.ingest is charged only its own time, not that of the spans
    # inside it (decrypt, fold, writeback)
    assert gaps["compact.ingest"] < 1.847517
    assert gaps[tr.NO_SPAN] > 0


def test_interval_arithmetic():
    iv = np.array([[5., 7.], [0., 2.], [1., 3.], [6., 6.5]])
    assert tr.union(iv).tolist() == [[0., 3.], [5., 7.]]
    windows = np.array([[2., 6.]])
    assert tr.clip(tr.union(iv), windows).tolist() == [[2., 3.], [5., 6.]]
    assert tr.complement(tr.union(iv), windows).tolist() == [[3., 5.]]
    assert tr.union(np.empty((0, 2))).shape == (0, 2)
    spans = [["outer", 0., 10e9], ["inner", 2e9, 3e9], ["zero", 4e9, 0.]]
    charged = tr.charge_gaps(np.array([[1e9, 6e9], [11e9, 12e9]]), spans)
    assert charged == {"outer": 2.0, "inner": 3.0, tr.NO_SPAN: 1.0}


def test_short_names_drop_what_varies_between_compiles():
    hlo = "%fusion.14 = s32[1025]{0:T(1024)S(1)} fusion(s32[65536]{0} %x), kind=kCustom"
    assert tr.short_name(hlo) == "fusion.14"
    assert tr.short_name("jit__fold_ablk(3137935763322559881)") == "jit__fold_ablk"
    assert tr.short_name("compact.gc") == "compact.gc"


def test_least_bytes_of_the_fold_at_two_known_shapes():
    # one add to one cell: 13 bytes of columns, 2 planes x (read + write) of
    # one word, one clock word read and written
    assert kernel_bytes.orset_fold(rows=1, cells=1, actors=1) == 13 + 16 + 8
    # a backlog round: 48,000 rows of 1,000 devices; at most one cell a row
    full = kernel_bytes.orset_fold(rows=48_000, cells=47_300, actors=1_000)
    assert full == 13 * 48_000 + 16 * 47_300 + 8 * 1_000 == 1_388_800
    # far under one pass over the dense planes (2 x 16.4 MB, read and written)
    assert full < 2 * 2 * 4 * 4096 * 1000


def test_readers_over_the_recorded_trace(recorded):
    window = {"trace": recorded, "calls": 2,
              "shapes": [{"rows": 48_000, "cells": 47_300, "actors": 1_000}] * 2,
              "peaks": {"hbm_bytes_per_s": 819e9}}
    args = {"line": "XLA Modules", "match": ["_fold_ablk"]}
    assert trace_kernel_ms.read(window, args) == pytest.approx(0.841951)
    share = roofline_pct.read(
        window, {**args, "bytes_fn": "orset_fold", "peak": "hbm_bytes_per_s"})
    assert share == pytest.approx(
        100 * 2 * 1_388_800 / 819e9 / 0.001683902)
    assert 0 < share < 100
    assert trace_kernel_ms.read({**window, "trace": None}, args) is None
    assert trace_kernel_ms.read(window, {**args, "match": ["absent"]}) is None
