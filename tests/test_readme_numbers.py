"""README §8b accuracy claims must trace to ``ACCURACY.json``.

The device rates §8b used to quote went with their record files in PR 21
(device numbers live in PERF_LEDGER.jsonl / PERF.md); what the section still
states as numbers are convergence results, and each must appear in
``ACCURACY.json``.  A "claim" is a decimal with >= 2 fractional digits
(``0.7732``); it must equal an artifact number rounded to the same number
of places.
"""

import json
import os
import re

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _artifact_numbers():
    vals = []

    def walk(o):
        if isinstance(o, dict):
            for v in o.values():
                walk(v)
        elif isinstance(o, (list, tuple)):
            for v in o:
                walk(v)
        elif isinstance(o, bool):
            pass
        elif isinstance(o, (int, float)):
            vals.append(float(o))
        elif isinstance(o, str):
            # numbers embedded in note/unit strings still count as recorded
            for m in re.findall(r"-?\d+\.?\d*(?:[eE]-?\d+)?", o):
                vals.append(float(m))

    with open(os.path.join(_REPO, "ACCURACY.json")) as f:
        walk(json.load(f))
    return vals


def _perf_section():
    with open(os.path.join(_REPO, "README.md")) as f:
        md = f.read()
    assert "## 8b." in md, "README §8b (performance notes) went missing"
    return md.split("## 8b.")[1].split("\n## ")[0]


def test_readme_accuracy_numbers_trace_to_artifact():
    sec = _perf_section()
    claims = set(re.findall(r"\d+\.\d{2,}", sec))
    # guard the extractor: a format change must not turn this into a no-op
    assert len(claims) >= 4, f"only {len(claims)} decimal claims found"
    vals = _artifact_numbers()
    untraced = []
    for s in claims:
        places = len(s.split(".")[1])
        if not any(abs(round(v, places) - float(s)) < 0.5 * 10 ** (-places)
                   for v in vals):
            untraced.append(s)
    assert not untraced, (
        f"README §8b claims with no recording in ACCURACY.json: "
        f"{sorted(untraced)}")


def test_readme_quotes_no_device_rate():
    """No thousands-separated figure (the form every old rate took) is left
    in §8b: device numbers are in the ledger, or 'not measured'."""
    assert not re.findall(r"\d{1,3}(?:,\d{3})+", _perf_section())
