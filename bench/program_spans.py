"""The program's own spans of the compile path (``repro.core.spans``), as the
set-up metrics read them in the traced run's process, after its set-up.  A
program without that recorder reads as no spans, and its metrics as None."""


def spans(name: str) -> list:
    """The recorded spans called ``name``."""
    try:
        from repro.core.spans import records
    except ImportError:
        return []
    return [s for s in records() if s.name == name]


def seconds(name: str) -> float | None:
    """Summed length of the spans called ``name``; None if there are none."""
    found = spans(name)
    return sum(s.seconds for s in found) if found else None
