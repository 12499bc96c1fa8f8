"""Phase `between` per tick: from the last tick's end to this tick's entry
while work was waiting — the serving loop's executor hop per tick
(llm/serving.py `_drive`) and whatever else held the stepping thread."""
from benchmarks.harness import tickphases


def read(record):
    return tickphases.phase_ms(record, "between")
