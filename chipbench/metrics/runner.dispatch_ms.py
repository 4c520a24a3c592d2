"""Host time per chunk that the chunk runner takes to dispatch a chunk
(the ``runner.dispatch`` span of ``ChunkRunner.__call__``: de-aliasing the
state and enqueueing the chunk program), the mean over the chunks of the
traced window, in ms, read from the traced run's profile."""

from chipbench import scopes as S


def read(ctx):
    path = S.latest_trace()
    if path is None:
        return None
    return S.mean_span_ms(S.program_spans(path), "runner.dispatch",
                          ctx["lo"], ctx["hi"])
