#!/usr/bin/env python
"""Static hot-path auditor CLI — drives repro.analysis.audit + jaxlint.

Compiles (never executes) every hot-path step factory and checks the
optimized HLO / jaxpr invariants: donation aliasing, pallas gather budget,
dtype discipline, and roofline conformance against hwmodel.attention_costs.
Exits non-zero when any unsuppressed finding remains.

Usage:
    python scripts/audit_steps.py                      # single-device matrix
    python scripts/audit_steps.py --matrix mesh        # forced-8-device matrix
    python scripts/audit_steps.py --matrix all --json out.json
    python scripts/audit_steps.py --lint-only          # AST pass only

``--matrix mesh`` (and ``all``) force ``XLA_FLAGS=
--xla_force_host_platform_device_count=8`` BEFORE jax initializes — run it
in a fresh process (the Makefile ``audit`` lane and tests/test_audit.py
both spawn it that way).
"""

import argparse
import json
import os
import sys


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    p.add_argument(
        "--matrix",
        choices=("single", "mesh", "all", "none"),
        default="single",
        help="which step matrix to compile (mesh forces 8 host devices)",
    )
    p.add_argument(
        "--lint",
        action="store_true",
        default=None,
        help="run the jaxlint AST pass (default: on for single/all)",
    )
    p.add_argument(
        "--no-lint", dest="lint", action="store_false", help="skip jaxlint"
    )
    p.add_argument(
        "--lint-only",
        action="store_true",
        help="shorthand for --matrix none --lint",
    )
    p.add_argument(
        "--lint-root",
        default=None,
        help="directory tree for jaxlint (default: src/repro next to repo root)",
    )
    p.add_argument("--json", default=None, help="write findings as JSON here")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(sys.argv[1:] if argv is None else argv)
    if args.lint_only:
        args.matrix, args.lint = "none", True
    if args.lint is None:
        args.lint = args.matrix in ("single", "all", "none")

    # the audit budgets are calibrated on CPU, with the Pallas kernels in
    # the interpreter (repro.kernels.interpret)
    os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
    if args.matrix in ("mesh", "all"):
        # must land before jax (imported transitively below) initializes
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo_root, "src"))
    from repro.analysis import audit, jaxlint

    findings = []
    specs = []
    if args.matrix in ("single", "all"):
        specs += audit.single_device_matrix()
    if args.matrix in ("mesh", "all"):
        specs += audit.mesh_matrix()
    for spec in specs:
        print(f"[audit] compiling {spec.where}", flush=True)
        findings += audit.audit_step(spec)
    if args.lint:
        root = args.lint_root or os.path.join(repo_root, "src", "repro")
        print(f"[audit] jaxlint over {root}", flush=True)
        findings += jaxlint.lint_tree(root)

    kept, suppressed = audit.split_allowlisted(findings)
    for f in suppressed:
        print(f"[audit] suppressed (allowlist): {f}")
    for f in kept:
        print(f"[audit] FINDING {f}")
    print(
        f"[audit] {len(specs)} cells compiled, {len(kept)} findings, "
        f"{len(suppressed)} suppressed"
    )
    if args.json:
        payload = {
            "findings": [vars(f) for f in kept],
            "suppressed": [vars(f) for f in suppressed],
            "cells": [s.where for s in specs],
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2)
    return 1 if kept else 0


if __name__ == "__main__":
    sys.exit(main())
