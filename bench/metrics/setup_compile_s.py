"""Seconds of set-up spent compiling or loading compiled programs from
the persistent cache: every ``compile.<function>`` span the program
recorded before the window started. None where the program records
none."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [t0 for name, t0, _ in run.spans.spans if name == "window"]
    rec = obs.window(float("-inf"), w[-1]) if w else {}
    secs = [s for name, (_, s) in rec.items() if name.startswith("compile.")]
    return sum(secs) if secs else None
