"""Host seconds per refresh cycle of ``SuggestFrontend.poll``: finding,
reading, unpacking and blending the newest persisted table."""


def read(run):
    n = run.spans.count("poll")
    return run.spans.total("poll") / n if n else None
