"""Phase `state` per tick: the `write_state` dispatches that install a
finished prefill's recurrent state into its row of the state pool."""
from benchmarks.harness import tickphases


def read(record):
    if "state_installs" not in record["closed"]["stats"]:
        return None
    return tickphases.phase_ms(record, "state")
