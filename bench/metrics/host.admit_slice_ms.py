"""Host time per dispatch spent assembling the batch's LPs and slicing
frontiers back out: ``serving.admit`` plus ``serving.slice`` spans, ms."""
from bench import readers


def read(obs):
    n = len(readers.spans(obs, "serving.dispatch"))
    if not n:
        return None
    host = (sum(readers.span_seconds(obs, "serving.admit"))
            + sum(readers.span_seconds(obs, "serving.slice")))
    return host / n * 1e3
