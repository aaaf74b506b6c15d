"""Minimal Chrome/Perfetto ``trace_event`` JSON schema checker.

Not a full validator — just the invariants the Perfetto UI and
chrome://tracing actually require to load a "JSON Array Format" trace:
a ``traceEvents`` list whose members carry the right fields per phase.
Raises AssertionError with a pointed message on the first violation so a
failing test names the bad event.
"""
from typing import Any, Dict

# phases we emit; "X"=complete, "i"=instant, "M"=metadata
_KNOWN_PHASES = {"X", "i", "M", "B", "E"}


def check_event(ev: Dict[str, Any], idx: int) -> None:
    assert isinstance(ev, dict), f"event[{idx}] is not an object: {ev!r}"
    ph = ev.get("ph")
    assert ph in _KNOWN_PHASES, f"event[{idx}] bad phase {ph!r}"
    assert isinstance(ev.get("name"), str) and ev["name"], \
        f"event[{idx}] missing name"
    assert isinstance(ev.get("pid"), int), f"event[{idx}] missing int pid"
    assert isinstance(ev.get("tid"), int), f"event[{idx}] missing int tid"
    if ph == "M":
        assert ev["name"] in ("thread_name", "process_name"), \
            f"event[{idx}] unknown metadata {ev['name']!r}"
        assert isinstance(ev.get("args", {}).get("name"), str), \
            f"event[{idx}] metadata without args.name"
        return
    ts = ev.get("ts")
    assert isinstance(ts, int) and ts >= 0, \
        f"event[{idx}] ts must be a non-negative int (µs), got {ts!r}"
    if ph == "X":
        dur = ev.get("dur")
        assert isinstance(dur, int) and dur > 0, \
            f"event[{idx}] complete event needs positive int dur, got {dur!r}"
    if ph == "i":
        assert ev.get("s", "t") in ("t", "p", "g"), \
            f"event[{idx}] bad instant scope {ev.get('s')!r}"
    args = ev.get("args", {})
    assert isinstance(args, dict), f"event[{idx}] args not an object"


def check_trace(trace: Dict[str, Any]) -> int:
    """Validate a trace dict; returns the number of events checked."""
    assert isinstance(trace, dict), "trace root must be an object"
    events = trace.get("traceEvents")
    assert isinstance(events, list), "traceEvents must be a list"
    for i, ev in enumerate(events):
        check_event(ev, i)
    return len(events)


# ---------------------------------------------------------------------------
# the closed span vocabulary (docs/observability.md "Span vocabulary")
# ---------------------------------------------------------------------------

def documented_span_names(doc_text: str) -> set:
    """Names in the first column of the vocabulary table: every back-quoted
    name of a row's first cell, with ``:<...>`` / ``<...>`` parts cut
    (``dag:<name>`` -> ``dag``, ``kernel.<Kernel.name>`` -> ``kernel.``)."""
    import re
    section = doc_text.split("### Span vocabulary", 1)[1]
    section = section.split("\n### ", 1)[0]
    names = set()
    for line in section.splitlines():
        if not line.startswith("| ") or line.startswith("| span |") or \
                line.startswith("|---"):
            continue
        first = line.split("|")[1]
        for name in re.findall(r"`([^`]+)`", first):
            names.add(name.split(":", 1)[0].split("<", 1)[0])
    return names


def undocumented_spans(span_names, doc_text: str) -> set:
    """Span names (as recorded) that the vocabulary table does not hold.
    A documented name that ends in ``.`` is a prefix (``kernel.``)."""
    documented = documented_span_names(doc_text)
    prefixes = tuple(n for n in documented if n.endswith("."))
    missing = set()
    for raw in span_names:
        name = raw.split(":", 1)[0]
        if name in documented or (prefixes and name.startswith(prefixes)):
            continue
        missing.add(name)
    return missing


# ---------------------------------------------------------------------------
# histograms and counters (docs/observability.md tables outside the span
# vocabulary)
# ---------------------------------------------------------------------------

def undocumented_metrics(names, doc_text: str) -> set:
    """Histogram and counter names that no table row of the document opens
    with: a row's first cell holds the name back-quoted."""
    import re
    documented = set()
    for line in doc_text.splitlines():
        if line.startswith("| `"):
            documented.update(re.findall(r"`([^`]+)`", line.split("|")[1]))
    return {name for name in names if name not in documented}


def undocumented_span_args(span_name: str, arg_names, doc_text: str) -> set:
    """Arguments of one recorded span that its row of the vocabulary table
    does not name back-quoted (``kernel.compile``: `kernel`, `signature`,
    `sort_ops`)."""
    section = doc_text.split("### Span vocabulary", 1)[1]
    section = section.split("\n### ", 1)[0]
    rows = [line for line in section.splitlines()
            if line.startswith("| ") and f"`{span_name}`" in line.split("|")[1]]
    assert len(rows) == 1, f"{span_name}: {len(rows)} vocabulary rows"
    return {a for a in arg_names if f"`{a}`" not in rows[0]}


def undocumented_counters(names, doc_text: str) -> set:
    """Counter names that the paragraphs after the span vocabulary table
    ("Counters counted where the work happens") do not name back-quoted."""
    section = doc_text.split("Counters counted where the work happens", 1)[1]
    section = section.split("\n### ", 1)[0]
    return {name for name in names if f"`{name}`" not in section}
