"""The ``resnet50-tinyimagenet`` configuration through the harness at a
size a test run holds, on the CPU: ResNet-50 at its widths on 16x16
images with the configuration's 200 classes, 4 clients, batches of 8 and
2 local steps, under FedGKD, is ``correct`` against the plain reference
within the cell's limits; and the FLOP count and the grouped-conv
roofline see its 53 convolutions."""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402
from bench.flops import grouped_conv, resnet  # noqa: E402
from bench.trace import Kernel  # noqa: E402

CELL = "resnet50-tinyimagenet.fedgkd"
SEED = 2 ** 31 + 54321


def test_tiny_resnet50_run_is_correct():
    cell = harness.load_cell(CELL)
    cfg = dict(cell["cfg"], image_hw=16, n_clients=4, participation=0.5,
               train_size=64, test_size=32, batch_size=8)
    assert traffic.local_steps(cfg) == 2
    tr = dict(cell["traffic"], warmup_rounds=3, trace_rounds=1)
    out = harness.run_cell(cfg, tr, cell["limits"], seed=SEED, seconds=0.5,
                           trace=False, t0=time.perf_counter())
    assert out["correct"], json.dumps(out["checks"])
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_flops_count_53_convolutions():
    cfg = harness.load_cell(CELL)["cfg"]
    layers = resnet.layers(cfg)
    assert len(resnet.convs(cfg)) == 53
    assert sum(not layer.dense for layer in layers) == 53
    stem = resnet.convs(cfg)[0]
    assert (stem.k, stem.stride, stem.cin, stem.cout) == (7, 2, 3, 64)
    assert max(layer.cout for layer in layers if not layer.dense) == 2048
    # 0.593 GFLOP a 64x64 forward pass
    assert abs(resnet.forward_flops(cfg) / 1e9 - 0.593) < 0.005


def test_grouped_conv_roofline_matches_53_calls():
    """One forward's kernel calls, one per convolution, as a trace shows
    them (4 clients of 64 examples, channels padded to 128 lanes): the
    roofline matches all 53 to the model's layers, and reads nothing for
    a count the model does not explain."""
    cfg = harness.load_cell(CELL)["cfg"]
    pad = lambda c: -(-c // 128) * 128
    calls = [Kernel([(4, 64, layer.stride ** 2, layer.hw ** 2 // layer.stride
                      ** 2, pad(layer.cin)),
                     (4, layer.k, layer.k, pad(layer.cin), pad(layer.cout))],
                    [(4, 64, layer.out_hw ** 2, pad(layer.cout))], 1e-4)
             for layer in resnet.convs(cfg)]
    peaks = harness.device_peaks("TPU v5 lite")
    share = grouped_conv.roofline(cfg, calls, 53, peaks)
    assert share is not None and 0 < share < 1
    assert grouped_conv.roofline(cfg, calls[:-1], 52, peaks) is None
