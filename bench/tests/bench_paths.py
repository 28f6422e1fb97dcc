"""Imported first by the benchmark's tests: puts bench/ and src/ on the
path, and holds the tiny cell the CPU can run."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a cell small enough for the CPU: the published block (widths from 64,
# eight-channel groups) at two stages and 8x8 images, 6 clients
TINY = dict(cnn_stages=[1], cnn_width=64, image_size=8, num_clients=6,
            client_sample_ratio=0.5, peers_per_round=2, probe_size=4,
            batch_size=8, samples_per_class=10, steps_per_epoch=1,
            epochs_extractor=1, epochs_header=1, chunk_rounds=2)


def tiny_cell(name: str) -> dict:
    """Cell `name` of BENCHMARK.json at the tiny size."""
    import workload

    cell = workload.cell(name)
    cell["sizes"].update(TINY)
    return cell
