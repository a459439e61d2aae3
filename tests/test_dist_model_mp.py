"""Cross-process DistModel: one OS process per pipeline stage,
activations over sockets (reference dist_model.cc one-rank-per-process
serving over brpc; here inference/dist_model_mp.py)."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.jit.static_function import InputSpec


def _export_stages(tmp_path, width=64, mb_rows=4):
    paddle.seed(0)

    class Stage1(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(8, width)
            self.fc2 = nn.Linear(width, width)

        def forward(self, x):
            return nn.functional.relu(self.fc2(
                nn.functional.relu(self.fc1(x))))

    class Stage2(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(width, width)
            self.fc2 = nn.Linear(width, 4)

        def forward(self, h):
            return self.fc2(nn.functional.relu(self.fc1(h)))

    s1, s2 = Stage1(), Stage2()
    s1.eval(), s2.eval()
    p1 = str(tmp_path / "stage1")
    p2 = str(tmp_path / "stage2")
    paddle.jit.save(s1, p1, input_spec=[
        InputSpec([mb_rows, 8], "float32", name="x")])
    paddle.jit.save(s2, p2, input_spec=[
        InputSpec([mb_rows, width], "float32", name="h")])
    return (s1, s2), (p1, p2)


def test_two_process_two_stage_parity(tmp_path):
    from paddle_tpu.inference.dist_model_mp import (DistModelMP,
                                                    DistModelConfig)
    (s1, s2), (p1, p2) = _export_stages(tmp_path)
    x = np.random.RandomState(1).randn(16, 8).astype(np.float32)
    ref = s2(s1(paddle.to_tensor(x))).numpy()
    with DistModelMP(DistModelConfig([p1, p2],
                                     num_micro_batches=4)) as dm:
        outs = dm.run([x])
        np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-5)
        # second batch over the SAME live pipeline (persistent sockets)
        outs2 = dm.run([x * 2.0])
        ref2 = s2(s1(paddle.to_tensor(x * 2.0))).numpy()
        np.testing.assert_allclose(outs2[0], ref2, rtol=1e-5, atol=1e-5)


def test_single_stage_process_roundtrip(tmp_path):
    from paddle_tpu.inference.dist_model_mp import (DistModelMP,
                                                    DistModelConfig)
    (s1, _), (p1, _) = _export_stages(tmp_path)
    x = np.random.RandomState(2).randn(8, 8).astype(np.float32)
    ref = s1(paddle.to_tensor(x)).numpy()
    with DistModelMP(DistModelConfig([p1],
                                     num_micro_batches=2)) as dm:
        np.testing.assert_allclose(dm.run([x])[0], ref,
                                   rtol=1e-5, atol=1e-5)


def test_bad_batch_raises(tmp_path):
    from paddle_tpu.inference.dist_model_mp import (DistModelMP,
                                                    DistModelConfig)
    _, (p1, p2) = _export_stages(tmp_path)
    with DistModelMP(DistModelConfig([p1, p2],
                                     num_micro_batches=4)) as dm:
        with pytest.raises(ValueError):
            dm.run([np.zeros((6, 8), np.float32)])  # 6 % 4 != 0


def test_int8_precision_composes_across_processes(tmp_path):
    # Weak#6 (round 3): int8 serving never composed with DistModel.
    # Each stage process applies PrecisionType.Int8 to its own
    # partition; parity vs the fp32 pipeline within int8 tolerance.
    from paddle_tpu import inference
    from paddle_tpu.inference.dist_model_mp import (DistModelMP,
                                                    DistModelConfig)
    (s1, s2), (p1, p2) = _export_stages(tmp_path, width=128)
    x = np.random.RandomState(3).randn(8, 8).astype(np.float32)
    ref = s2(s1(paddle.to_tensor(x))).numpy()
    with DistModelMP(DistModelConfig(
            [p1, p2], num_micro_batches=2,
            precision=inference.PrecisionType.Int8)) as dm:
        got = dm.run([x])[0]
    scale = np.max(np.abs(ref))
    assert np.max(np.abs(got - ref)) < 0.05 * scale + 1e-3


def test_stage_overlap_arithmetic(tmp_path, monkeypatch):
    """The credit-window pipeline OVERLAPS stages: with a per-micro-
    batch dwell D injected into every stage worker (PTPU_STAGE_DWELL_MS
    — sleeps overlap even on a 1-core host, where CPU-bound compute
    cannot), M micro-batches through S stages must take ~(M + S - 1) x D,
    not the serial M x S x D. This pins the favorable regime the +63%
    1-core serving tax (the rounds-1-5 notes (git history before PR 23)) cannot
    show."""
    from paddle_tpu.inference.dist_model_mp import (DistModelMP,
                                                    DistModelConfig)
    _, (p1, p2) = _export_stages(tmp_path)
    # D = 0.15 (not 0.06): fixed per-message socket/pickle/compute
    # overhead on a loaded 1-core CI host rides ON TOP of the sleeps;
    # the dwell must dominate it or the 0.8*serial bound goes flaky
    M, S, D = 6, 2, 0.15
    monkeypatch.setenv("PTPU_STAGE_DWELL_MS", str(int(D * 1000)))
    # explicit debug marker: the dwell is gated out of production
    # serving (cpu-platform or marker only — dist_model_mp.py)
    monkeypatch.setenv("PTPU_STAGE_DWELL_DEBUG", "1")
    x = np.random.RandomState(2).randn(4 * M, 8).astype(np.float32)
    with DistModelMP(DistModelConfig([p1, p2],
                                     num_micro_batches=M)) as dm:
        dm.run([x])                       # warm the pipeline
        t0 = time.perf_counter()
        dm.run([x])
        wall = time.perf_counter() - t0
    serial = M * S * D
    pipelined = (M + S - 1) * D
    # must beat serial decisively and cannot beat the schedule bound
    assert wall < 0.8 * serial, (wall, serial)
    assert wall >= pipelined * 0.9, (wall, pipelined)
