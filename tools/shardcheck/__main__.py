"""CLI: ``python -m tools.shardcheck [--validate] [--grid G] [--entry E]``.

Exit 0 when every manifest entry abstract-traces cleanly over every
AbstractMesh grid AND every named engine jit site is registered;
exit 1 on any trace failure, contract-check failure, or coverage gap.
``--validate`` runs only the offline checks (no JAX import) — the
fast pre-commit half of the gate.
"""

import argparse
import os
import sys
import traceback
from dataclasses import dataclass
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent.parent
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from tools.shardcheck.manifest import (  # noqa: E402
    GRIDS,
    MANIFEST,
    Entry,
    coverage_failures,
    make_ctx,
    validate_manifest,
)


@dataclass
class Result:
    entry: str
    grid: str
    status: str  # "pass" | "fail"
    detail: str = ""


def run_entry(entry: Entry, grid: str, ctx=None) -> Result:
    """Abstract-trace one entry over one grid (device-free)."""
    import jax

    ctx = make_ctx(grid) if ctx is None else ctx
    try:
        fn, args, kwargs = entry.build(ctx)
        out = jax.eval_shape(fn, *args, **kwargs)
        entry.check(ctx, out)
    except Exception as e:  # trace/shape/axis failures are the product
        tb = traceback.format_exc(limit=3)
        return Result(
            entry.name, grid, "fail", f"{type(e).__name__}: {e}\n{tb}"
        )
    return Result(entry.name, grid, "pass")


def run_all(
    grids=None, entries=None, verbose: bool = False
) -> list[Result]:
    results = []
    for grid in grids or GRIDS:
        ctx = make_ctx(grid)
        for name in entries or MANIFEST:
            r = run_entry(MANIFEST[name], grid, ctx)
            results.append(r)
            if verbose or r.status != "pass":
                line = f"[{r.status.upper():4}] {grid:8} {name}"
                if r.detail:
                    line += f" — {r.detail.splitlines()[0]}"
                print(line, file=sys.stderr)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="shardcheck",
        description="device-free SPMD verification of the serve jit "
        "surface over AbstractMesh grids (docs/reference/lint.md)",
    )
    ap.add_argument(
        "--validate",
        action="store_true",
        help="offline checks only: manifest well-formedness + engine "
        "jit-site coverage (no JAX import, no tracing)",
    )
    ap.add_argument(
        "--grid", choices=sorted(GRIDS), action="append",
        help="run only this mesh grid (repeatable; default: all)",
    )
    ap.add_argument(
        "--entry", choices=sorted(MANIFEST), action="append",
        help="run only this manifest entry (repeatable; default: all)",
    )
    ap.add_argument(
        "-v", "--verbose", action="store_true",
        help="print every entry's status, not just failures",
    )
    args = ap.parse_args(argv)

    problems = validate_manifest() + coverage_failures()
    for p in problems:
        print(f"shardcheck: {p}", file=sys.stderr)

    if args.validate:
        n = len(MANIFEST)
        if not problems:
            print(
                f"shardcheck --validate ok: {n} entries, "
                f"{len(GRIDS)} grids, engine coverage complete"
            )
        return 1 if problems else 0

    # the abstract-trace pass needs CPU only — pin it so a
    # TPU-initialized environment cannot make this gate device-bound
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    results = run_all(args.grid, args.entry, verbose=args.verbose)
    failed = [r for r in results if r.status == "fail"]
    passed = [r for r in results if r.status == "pass"]
    for r in failed:
        print(f"\nFAIL {r.grid}/{r.entry}:\n{r.detail}", file=sys.stderr)
    print(
        f"shardcheck: {len(passed)} passed, {len(failed)} failed "
        f"across {len(args.grid or GRIDS)} grid(s)"
        + (f"; {len(problems)} coverage/validation problem(s)" if problems else "")
    )
    return 1 if (failed or problems) else 0


if __name__ == "__main__":
    sys.exit(main())
