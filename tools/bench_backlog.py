#!/usr/bin/env python
"""Backlog validator: every queued bench command must still run.

The BENCH_MEASURED_r*.json rounds carry ``queued_measurements_r*``
lists — on-chip commands written rounds ago, waiting for silicon.  Rows
get renamed, flags change, models get re-registered; a queued command
referencing a vanished row name would silently burn its measurement
window.  This tool re-validates the WHOLE queue against the current
tree (run from tier-1 via tests/test_telemetry.py):

- ``python bench.py`` invocations: every ``--flag`` must appear in
  bench.py, ``--row`` names must be registered in ``bench._ROWS``,
  ``--peak-entry`` indices must be inside the ladder.
- ``python tools/<script>.py`` invocations: the script must exist and
  every ``--flag`` must appear in its source.
- ``python -``/``python -c`` snippet bodies are validated leniently:
  any ``get_model_config('name')`` reference must resolve against the
  models registry.
- env-prefixed and ``for ...; do ...; done`` wrapped commands are
  unwrapped first; ``see BENCH_MEASURED_...`` cross-references must
  point at an existing round file.
- staleness: every on-chip row the run ledger flags as ``stale``
  (carried forward since r04 — telemetry/ledger.py
  ``LAST_MEASURED_ROUND``) must have a re-measurement command attached,
  and that command must itself pass the checks above.  The stale set is
  printed with its commands so the next silicon window has a ready-made
  worklist (same view as ``tools/obs_report.py``).

Exit 1 with one line per finding; exit 0 when the queue is clean (the
stale-row worklist is informational, not a finding).
"""

from __future__ import annotations

import glob
import json
import os
import re
import shlex
import sys
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# rounds before r07 predate the queued-command grammar (r04 is a
# measurement record, r05/r06 queues were drained and superseded)
ROUND_GLOB = "BENCH_MEASURED_r*.json"
FIRST_VALIDATED_ROUND = 7

_ENV_TOKEN = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*=\S*$")
_MODEL_REF = re.compile(r"get_model_config\(\s*['\"]([^'\"]+)['\"]")
_FOR_LOOP = re.compile(r"^for\s+\w+\s+in\s+[^;]+;\s*do\s+(.*?);?\s*done$")


def _bench_rows():
    """bench._ROWS / ladder length without importing jax eagerly —
    bench.py only touches the backend under --smoke, so a plain import
    from the repo root is safe and keeps the row list authoritative."""
    import bench

    return set(bench._ROWS), len(bench._PEAK_LADDER)


def _strip_comment(cmd: str) -> str:
    # queued cmds annotate with trailing "  # ..." notes; heredoc bodies
    # ('\n' present) keep their hash lines
    if "\n" in cmd:
        return cmd
    return cmd.split("  #", 1)[0].strip()


def _segments(cmd: str) -> List[str]:
    """Unwrap env prefixes / for-loops and split on top-level ``&&``."""
    out = []
    for seg in cmd.split("&&"):
        seg = seg.strip()
        m = _FOR_LOOP.match(seg)
        if m:
            seg = m.group(1).strip()
        try:
            toks = shlex.split(seg.split("\n", 1)[0])
        except ValueError:
            toks = seg.split()
        while toks and _ENV_TOKEN.match(toks[0]):
            toks = toks[1:]
        if toks:
            out.append(" ".join(toks) + ("\n" + seg.split("\n", 1)[1]
                                         if "\n" in seg else ""))
    return out


def _check_snippet(body: str, where: str, errors: List[str]) -> None:
    from deepspeed_tpu.models.registry import list_models

    known = set(list_models())
    for name in _MODEL_REF.findall(body):
        if name not in known:
            errors.append(f"{where}: snippet references unknown model "
                          f"{name!r} (known: {sorted(known)})")


def _check_bench(toks: List[str], where: str, rows, ladder_len,
                 errors: List[str]) -> None:
    src = open(os.path.join(REPO, "bench.py")).read()
    i = 0
    while i < len(toks):
        t = toks[i]
        if t == "--row":
            i += 1
            if i >= len(toks) or toks[i] not in rows:
                errors.append(f"{where}: unknown bench row "
                              f"{toks[i] if i < len(toks) else '<missing>'!r}"
                              f" (known: {sorted(rows)})")
        elif t == "--peak-entry":
            i += 1
            if i >= len(toks) or not toks[i].isdigit() \
                    or int(toks[i]) >= ladder_len:
                errors.append(f"{where}: --peak-entry index out of "
                              f"ladder range (< {ladder_len})")
        elif t.startswith("--") and t not in src:
            errors.append(f"{where}: bench.py has no flag {t!r}")
        i += 1


def _check_tool(toks: List[str], where: str, errors: List[str]) -> None:
    script = os.path.join(REPO, toks[0])
    if not os.path.exists(script):
        errors.append(f"{where}: script {toks[0]!r} does not exist")
        return
    src = open(script).read()
    for t in toks[1:]:
        if t.startswith("--") and t not in src:
            errors.append(f"{where}: {toks[0]} has no flag {t!r}")


def _check_cmd(cmd: str, where: str, rows, ladder_len,
               errors: List[str]) -> None:
    cmd = _strip_comment(cmd)
    if cmd.startswith("see "):
        ref = cmd.split()[1]
        if not os.path.exists(os.path.join(REPO, ref.split(".json")[0]
                                           + ".json")):
            errors.append(f"{where}: cross-reference {ref!r} missing")
        return
    for seg in _segments(cmd):
        toks = seg.split("\n", 1)[0].split()
        if not toks:
            continue
        if toks[0] == "git":
            continue
        if toks[0] != "python" and not toks[0].startswith("python"):
            errors.append(f"{where}: unrecognised command {toks[0]!r}")
            continue
        if len(toks) > 1 and toks[1] in ("-", "-c"):
            _check_snippet(seg, where, errors)
        elif len(toks) > 1 and toks[1] == "bench.py":
            _check_bench(toks[2:], where, rows, ladder_len, errors)
        elif len(toks) > 1 and toks[1].startswith("tools/"):
            _check_tool(toks[1:], where, errors)
        elif len(toks) == 1:
            pass  # bare "python bench.py" variants already matched above
        else:
            errors.append(f"{where}: unrecognised python target "
                          f"{toks[1]!r}")


def check_stale(rows, ladder_len, errors: List[str]):
    """Ledger staleness lint: every row still carrying an on-chip number
    measured at r04 must have a validated re-measurement command.
    Returns {row: cmd} for the worklist printout."""
    from deepspeed_tpu.telemetry import ledger

    history = ledger.load_bench_history(REPO)
    requeue = ledger.attach_requeue_cmds(
        history, ledger.collect_queued_cmds(REPO))
    for row, cmd in sorted(requeue.items()):
        where = f"stale[{row}]"
        if not cmd:
            errors.append(f"{where}: carried since "
                          f"r{ledger.LAST_MEASURED_ROUND:02d} with no "
                          f"re-measurement command attached")
            continue
        _check_cmd(cmd, where, rows, ladder_len, errors)
    return requeue


def run_all() -> List[str]:
    errors: List[str] = []
    rows, ladder_len = _bench_rows()
    seen_any = False
    for path in sorted(glob.glob(os.path.join(REPO, ROUND_GLOB))):
        fname = os.path.basename(path)
        rnum = int(re.search(r"_r(\d+)\.json$", fname).group(1))
        if rnum < FIRST_VALIDATED_ROUND:
            continue
        data = json.load(open(path))
        queued = data.get(f"queued_measurements_r{rnum:02d}")
        if not isinstance(queued, list):
            errors.append(f"{fname}: no queued_measurements_r{rnum:02d} "
                          f"list")
            continue
        for i, entry in enumerate(queued):
            where = f"{fname}[{i}]"
            if not isinstance(entry, dict) or "cmd" not in entry \
                    or "what" not in entry:
                errors.append(f"{where}: entry needs 'what' and 'cmd'")
                continue
            seen_any = True
            _check_cmd(entry["cmd"], where, rows, ladder_len, errors)
    if not seen_any and glob.glob(os.path.join(REPO, ROUND_GLOB)):
        errors.append("no queued commands found — backlog files moved?")
    check_stale(rows, ladder_len, errors)
    return errors


def main() -> int:
    errors = run_all()
    for e in errors:
        print(e)
    rows, ladder_len = _bench_rows()
    stale = check_stale(rows, ladder_len, [])
    if stale:
        print(f"stale rows ({len(stale)} carried forward; re-measure "
              f"with):")
        for row, cmd in sorted(stale.items()):
            print(f"  {row}: {cmd}")
    n = sum(1 for _ in glob.glob(os.path.join(REPO, ROUND_GLOB)))
    print(f"bench_backlog: {len(errors)} finding(s) across {n} round "
          f"file(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
