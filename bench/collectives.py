"""The collectives in a compiled program's HLO text, and what the coupled
sampler's structure check reads of them.

The elastically coupled sampler's one cross-chain collective is the mean of
the chains' positions, inside the sync branch of its s-periodic
``lax.cond``.  At the deployment's step size the chains move below the
resolution of the centre's state, so an exchange that is left out, or
moved, changes no trajectory over a few steps: only the program's
structure shows it.
"""
from __future__ import annotations

OTHER = ("all-gather", "all-to-all", "collective-permute", "reduce-scatter")
# the op_name path of an operation JAX placed in the true branch of a cond
SYNC_BRANCH = "/cond/branch_1_fun/"


def count(hlo_text: str) -> dict:
    """Collective instructions in compiled HLO text (an async start/done
    pair counts once): all-reduces, how many of them are in the sync
    branch, and every other collective."""
    ops = [ln for ln in hlo_text.splitlines() if " = " in ln]
    is_op = lambda ln, op: f" {op}(" in ln or f" {op}-start(" in ln
    reduces = [ln for ln in ops if is_op(ln, "all-reduce")]
    return {
        "all_reduce": len(reduces),
        "in_sync_branch": sum(SYNC_BRANCH in ln for ln in reduces),
        "other": sum(is_op(ln, op) for ln in ops for op in OTHER),
    }


def exchange_checks(hlo_text: str) -> dict:
    """``stray_collectives``: all-reduces outside the sync branch, and any
    other collective; ``missing_exchange``: 1 where the sync branch holds
    no all-reduce.  Both read 0 in a sound program."""
    c = count(hlo_text)
    return {"stray_collectives": c["all_reduce"] - c["in_sync_branch"] + c["other"],
            "missing_exchange": int(c["in_sync_branch"] == 0)}
