"""AOT (lower/compile) result normalization.

``Compiled.cost_analysis()`` returns a dict, or None where the backend
has no cost model; ``flatten_cost_analysis`` always hands back a dict,
so roofline/dryrun code never branches on the backend.
"""

from __future__ import annotations


def flatten_cost_analysis(cost) -> dict:
    """Normalize Compiled.cost_analysis() output to a flat dict."""
    return dict(cost) if cost else {}
