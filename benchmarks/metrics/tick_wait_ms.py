"""Phase `wait` per tick: the host blocked in np.asarray(out) on the device,
for this tick's un-fenced prefill chunk and its decode alike."""
from benchmarks.harness import tickphases


def read(record):
    return tickphases.phase_ms(record, "wait")
