"""Mean duration of an admission round that admitted at least one request
(span ``engine.admit``: queue pops, ledger, slot state reset), in ms."""
from bench.engine_spans import mean_ms


def read(run):
    return mean_ms(run, "engine.admit")
