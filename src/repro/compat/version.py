"""Version detection and the pinned-API canary.

``api_report()`` states which branch of each fallback chain resolved at
import time; ``check_pinned_api()`` raises if any chain resolved to no
known branch or the installed JAX is outside the supported range.  The
canary test calls both so a JAX bump fails the suite in exactly one
obvious place instead of as 59 scattered AttributeErrors.
"""

from __future__ import annotations

from typing import Tuple

import jax

# Inclusive lower bound, exclusive upper bound: the 0.9 series, the one
# release the suite and the chip runs use.  Widen the range only after
# re-running the full suite (scripts/check.sh) on the new release.
SUPPORTED_MIN: Tuple[int, int, int] = (0, 9, 0)
SUPPORTED_MAX: Tuple[int, int, int] = (0, 10, 0)


def _parse(version: str) -> Tuple[int, int, int]:
    """'0.9.0' / '0.10.0.dev20260101' -> (0, 9, 0) / (0, 10, 0)."""
    parts = []
    for tok in version.split(".")[:3]:
        digits = ""
        for ch in tok:
            if not ch.isdigit():
                break
            digits += ch
        parts.append(int(digits or 0))
    while len(parts) < 3:
        parts.append(0)
    return tuple(parts[:3])


JAX_VERSION: Tuple[int, int, int] = _parse(jax.__version__)

# Every fallback chain and the branch names it may resolve to.  A None
# branch means no candidate API exists in the installed JAX at all.
KNOWN_BRANCHES = {
    "mesh_introspection": {"get_abstract_mesh"},
    "mesh_activation": {"set_mesh"},
}


def supported() -> bool:
    return SUPPORTED_MIN <= JAX_VERSION < SUPPORTED_MAX


def api_report() -> dict:
    """Which branch each version-sensitive chain resolved to."""
    from repro.compat import meshes

    return {
        "jax": jax.__version__,
        "supported": supported(),
        "mesh_introspection": meshes.INTROSPECTION_BRANCH,
        "mesh_activation": meshes.ACTIVATION_BRANCH,
    }


def check_pinned_api() -> dict:
    """Raise RuntimeError unless every chain resolved to a known branch
    and the installed JAX is inside the supported range.  Returns the
    report on success so callers can log it."""
    report = api_report()
    problems = []
    if not report["supported"]:
        problems.append(
            f"jax {jax.__version__} outside supported range "
            f"[{'.'.join(map(str, SUPPORTED_MIN))}, "
            f"{'.'.join(map(str, SUPPORTED_MAX))})")
    for chain, known in KNOWN_BRANCHES.items():
        branch = report[chain]
        if branch not in known:
            problems.append(
                f"{chain}: resolved to {branch!r}, expected one of "
                f"{sorted(known)} — extend repro/compat for this JAX")
    if problems:
        raise RuntimeError(
            "repro.compat pinned-API canary failed:\n  "
            + "\n  ".join(problems))
    return report
