"""Mean time from the end of a decode program (``jit_gspmd_step``) to the
end of the engine's fetch of its logits (span ``engine.fetch``), in ms: the
slice, the copy to the host and the host's wake-up."""
from bench.engine_spans import logits_fetch_ms


def read(run):
    return logits_fetch_ms(run)
