"""``trace_reduce`` on a trace recorded on the chip: three steps of the
``mistral-7b-v0_3-l4.train-1chip`` job on one TPU v5e (my chip run, PR 23;
``--trace 1 --keep-trace``), kept gzipped under ``data/``. The known answers
are what that run printed."""

import gzip
import os
import shutil

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _unpack(name, tmp_path):
    out = tmp_path / "plugins" / "profile" / "recorded"
    out.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, name)) as src, open(out / "chip.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(tmp_path)


def test_one_chip_training_trace(tmp_path):
    r = trace_reduce.reduce_trace(_unpack("train_1chip_v5e.xplane.pb.gz", tmp_path))
    assert r["device_planes"] == 1
    assert r["steps"] == 3
    assert r["busy_s"] == pytest.approx(5.221493, rel=1e-5)
    assert r["window_s"] == pytest.approx(5.246635, rel=1e-5)
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(0.4792, abs=1e-3)
    assert r["exposed_collective_s"] == 0.0
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion" and "bitcast_dynamic-update-slice_fusion" in names
    # self times: the scan's `while` holds the layer bodies and must not
    # count them twice, so the top ten add up to no more than the busy time
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] * 1.0001
    assert sum(t for _, t in r["device_ops"]) > 0.9 * r["busy_s"]
    # the four longest gaps are the host's turn between steps, under the
    # trainer's StepTraceAnnotation
    assert all("host: train" in n for n, _ in r["idle_gaps"][:4])
    assert 0.004 < r["idle_gaps"][0][1] < 0.01
