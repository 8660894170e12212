"""The flag-identity pass: systematic enforcement of every registered
byte-identity contract.

The contract table is DECLARATIVE and lives where the flags live:
`utils/flags.py` registers `identity=<value>` on each flag whose
contract is "setting it to <value> lowers the canonical programs to
exactly what an unset environment lowers" (routing flags at their
neutral value, post-compile analysis flags at "1").  This pass replaced
the ~10 hand-written per-flag byte-identity tests of PRs 2/6/8/9: a new
flag gets enforcement by REGISTERING its contract, not by writing a
test.

Mechanics: build and lower each canonical program (analysis/programs.py)
once with every contracted flag UNSET — the baseline fingerprints —
while `flags.recorded_reads` notes which flags that build and trace
asked for.  Every read goes through an accessor that reads the
environment at the call (the env-bypass lint holds the first, `flags.py`
the second), so a program that never asked for a flag cannot depend on
it: that (flag, program) pair is held by the record, reported with
`"read": False`, and costs nothing.  A pair whose flag WAS read is built
and lowered again with exactly that flag set to its identity value, and
the sha256 fingerprints of the traced module text are compared.  Every
contract acts at build/trace time, so trace-level identity implies
compiled identity (and costs no XLA compile).

A mismatch is an ERROR finding carrying both fingerprints; the sweep
also returns its coverage rows so the acceptance test can assert 100%
of `flags.identity_flags()` is held against EVERY program.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Optional, Sequence

from hetu_tpu.analysis.findings import ERROR, INFO, Finding
from hetu_tpu.analysis.programs import PROGRAMS, scoped_env
from hetu_tpu.obs.hlo_text import without_source_positions


def fingerprint(text: str) -> str:
    """What "the same program" is compared on: a traced module's text,
    or a compiled one's less the source positions it was lowered from."""
    return hashlib.sha256(
        without_source_positions(text).encode()).hexdigest()[:16]


def identity_sweep(only_flags: Optional[Sequence[str]] = None,
                   programs: Optional[Sequence[str]] = None
                   ) -> Dict[str, Any]:
    """Run the sweep; returns {"baseline", "rows", "findings"}.

    rows: one {"flag", "value", "program", "read", "fingerprint", "ok"}
    per (contracted flag, program) pair — the coverage record; `read`
    says whether the program's build asked for the flag, and so whether
    the fingerprint is a second lower's or the baseline's.  findings:
    one ERROR per broken contract + one INFO summarizing the sweep.
    `only_flags` restricts the table (tools_lint --flags <name> for
    bisection); coverage claims are only made for what actually ran.
    """
    from hetu_tpu.utils import flags as _flags
    table = _flags.identity_flags()
    if only_flags:
        unknown = sorted(set(only_flags) - set(table))
        if unknown:
            raise ValueError(
                f"no identity contract registered for {unknown}; "
                f"contracted flags: {sorted(table)}")
        table = {k: v for k, v in table.items() if k in only_flags}
    prog_names = list(programs if programs is not None else PROGRAMS)

    # every contracted flag is held UNSET for the baseline and for the
    # other flags' variants — one variant differs from baseline by
    # exactly one variable
    all_unset = {name: None for name in _flags.identity_flags()}

    baseline: Dict[str, str] = {}
    reads: Dict[str, set] = {}
    with scoped_env(**all_unset):
        for prog in prog_names:
            with _flags.recorded_reads() as reads[prog]:
                baseline[prog] = fingerprint(PROGRAMS[prog]())

    rows: List[Dict[str, Any]] = []
    findings: List[Finding] = []
    for name, value in sorted(table.items()):
        for prog in prog_names:
            read = name in reads[prog]
            fp = baseline[prog]
            if read:
                with scoped_env(**{**all_unset, name: value}):
                    fp = fingerprint(PROGRAMS[prog]())
            ok = fp == baseline[prog]
            rows.append({"flag": name, "value": value, "program": prog,
                         "read": read, "fingerprint": fp, "ok": ok})
            if not ok:
                findings.append(Finding(
                    "flag-identity", ERROR, f"flag:{name}/{prog}",
                    f"{name}={value} must lower the {prog} program "
                    f"byte-identical to an unset environment, but the "
                    f"fingerprint moved ({baseline[prog]} -> {fp}) — "
                    f"the flag's neutral value is not neutral",
                    {"flag": name, "value": value, "program": prog,
                     "baseline": baseline[prog], "got": fp}))
    n_bad = sum(1 for r in rows if not r["ok"])
    n_read = sum(1 for r in rows if r["read"])
    findings.append(Finding(
        "flag-identity", INFO, "flag:sweep",
        f"{len(table)} contracted flags x {len(prog_names)} programs: "
        f"{len(rows) - n_bad}/{len(rows)} identities hold ({n_read} "
        f"lowered again, {len(rows) - n_read} whose program never reads "
        f"the flag)",
        {"flags": sorted(table), "programs": prog_names,
         "lowered": n_read, "violations": n_bad}))
    return {"baseline": baseline, "rows": rows, "findings": findings}
