"""Readers over the host's part of a serving round, as the scheduler counts
and names it.

The paged scheduler stamps the return of every blocking ``pull`` and, at the
next enqueue (the start of a ``prefill_chunk`` or of a ``dispatch``), adds
what has passed to the round's *host gap*: each ``round`` span carries it as
``host_gap_ms``.  The spans that lie in those gaps (``admit``,
``prefix_register``, ``first_token``, ``decode_prep``, ``commit``,
``round_metrics`` inside the round; ``claim`` and ``flush_outbox`` of the
server's loop between two rounds) say what the host was doing while the
device had nothing queued.

Everything is read from the capture (``spans.captured()``) over the rounds
that dispatched work.  A reader returns nothing where the capture holds no
such span, as on a program from before this counter, and also where the
recorder dropped spans of the session (``dropped_profiled``): a mean over a
truncated capture is not a reading.
"""

from __future__ import annotations

from typing import Optional

from benchmark.readers import spans

ENQUEUES = ("dispatch", "prefill_chunk")


def captured() -> list:
    """The capture, or ``[]`` where the recorder could not keep all of it."""
    from relora_tpu.obs import flight

    if getattr(flight.default_recorder(), "dropped_profiled", 0):
        return []
    return spans.captured()


def rounds(capture: list) -> list:
    """``spans.rounds`` of a scheduler that counts its host gap: one
    population of rounds for every reader here."""
    return [(r, below) for r, below in spans.rounds(capture) if "host_gap_ms" in r["attrs"]]


def mean_round_attr(obs: dict, attr: str) -> Optional[float]:
    """Mean of the ``round`` spans' attribute ``attr``."""
    values = [r["attrs"][attr] for r, _ in rounds(captured()) if attr in r["attrs"]]
    return sum(values) / len(values) if values else None


def span_ms_per_round(obs: dict, names: list) -> Optional[float]:
    """Summed milliseconds of the spans called one of ``names`` inside the
    rounds, over the number of rounds."""
    whole = rounds(captured())
    inside = [s["dur_s"] for _, below in whole for s in below if s["name"] in names]
    return 1e3 * sum(inside) / len(whole) if inside else None


def attr_ratio(obs: dict, names: list, numerator: str, denominator: str) -> Optional[float]:
    """Sum of one attribute over the sum of another, over the spans called
    one of ``names`` inside the rounds that carry both."""
    both = [
        s["attrs"] for _, below in rounds(captured()) for s in below
        if s["name"] in names and numerator in s["attrs"] and s["attrs"].get(denominator)
    ]
    return sum(a[numerator] for a in both) / sum(a[denominator] for a in both) if both else None


def _covered(intervals: list, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the union of ``intervals`` (sorted) covers."""
    total, reach = 0.0, lo
    for a, b in intervals:
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def host_gap_unspanned_ms(obs: dict, names: list) -> Optional[float]:
    """Mean per round of ``host_gap_ms`` less the time that spans called one
    of ``names`` cover in the round's gaps: what the tracing cannot name yet.

    A round's gaps are rebuilt from its own spans: inside it, from a ``pull``'s
    end to the next enqueue's start; before it, the stretch that ends at its
    first enqueue and is as long as what of ``host_gap_ms`` the inner gaps
    leave (nothing where the scheduler dropped it: an idle wait).  The spans
    of the whole capture are laid over them, so the last round's ``commit``
    and the loop's ``claim`` count where they fall in this round's lead; a
    round whose lead reaches back before the capture's first span is left out."""
    capture = captured()
    named = sorted((s["t_start"], s["t_end"]) for s in capture if s["name"] in names)
    session_start = min((s["t_start"] for s in capture), default=0.0)
    left = []
    for r, below in rounds(capture):
        marks = sorted(
            [(s["t_start"], True) for s in below if s["name"] in ENQUEUES]
            + [(s["t_end"], False) for s in below if s["name"] == "pull"]
        )
        gaps, opened = [], None
        for t, enqueue in marks:
            if not enqueue:
                opened = t
            elif opened is not None:
                gaps.append((opened, t))
                opened = None
        first = next((t for t, enqueue in marks if enqueue), None)
        lead = 1e-3 * r["attrs"]["host_gap_ms"] - sum(b - a for a, b in gaps)
        if first is not None and lead > 0:
            if first - lead < session_start - 1e-6:
                continue
            gaps.append((first - lead, first))
        left.append(r["attrs"]["host_gap_ms"] - 1e3 * sum(_covered(named, a, b) for a, b in gaps))
    return sum(left) / len(left) if left else None
