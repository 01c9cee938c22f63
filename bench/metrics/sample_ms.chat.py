"""Mean duration of the engine's sampling phase (span ``engine.sample``:
next token per resident slot, ledger, finished slots released), in ms."""
from bench.engine_spans import mean_ms


def read(run):
    return mean_ms(run, "engine.sample")
