"""beam_share: device time under the ``beam_search`` stage scope of the
serving executable, as a percentage of device busy time, inside the
traced span (``spans.stage_share``; ops carry the scope from
``spans.load_events``)."""

from bench import spans


def read(ctx):
    return spans.stage_share(ctx.events or [], "beam_search")
