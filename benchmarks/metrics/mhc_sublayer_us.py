"""Device time of ONE sublayer's `mhc/*` operations in a decode step, in
microseconds: a layer's (its two connections') over two, the median layer.
The chain's length: a few dozen small dependent kernels on the step's
critical path, which have no byte roofline worth the name."""
import statistics

from benchmarks.harness import serve_cell_xing_mhc as cell


def read(record):
    layers = cell.mhc_by_layer(record)
    if not layers:
        return None
    return 1e6 * statistics.median(layers) / 2.0
