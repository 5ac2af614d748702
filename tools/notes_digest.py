#!/usr/bin/env python
"""Digest BENCH_NOTES_r05.json into a human-readable summary: latest row
per metric, llama-bisect verdicts, flash A/B recommendations.
"""
import json
import os
import sys

NOTES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_NOTES_r05.json")


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else NOTES
    if not os.path.exists(path):
        print(f"no notes file at {path}")
        return 1
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                print(f"  (skipping malformed line: {line[:60]}...)")
    if not rows:
        print("notes file is empty")
        return 1

    print(f"=== digest of {os.path.basename(path)} ({len(rows)} rows) ===")

    # BEST row per headline metric (ladders append every rung — the last
    # rung is rarely the best); a TPU row is never displaced by a CPU row
    # (local smokes/fallbacks append after real evidence)
    latest = {}
    for r in rows:
        m = r.get("metric")
        if m and m not in ("llama_bisect", "flash_ab", "flash_ab_summary"):
            prev = latest.get(m)
            r_tpu = r.get("device") == "tpu"
            if prev is not None:
                p_tpu = prev.get("device") == "tpu"
                if p_tpu and not r_tpu:
                    continue
                # best-by-value only for throughput metrics (ladders
                # append every rung); memory/size metrics (GiB — lower
                # is better, one row per combo) keep last-wins
                if (p_tpu == r_tpu
                        and r.get("unit") in ("tokens/s", "imgs/s")
                        and isinstance(prev.get("value"), (int, float))
                        and isinstance(r.get("value"), (int, float))
                        and r["value"] <= prev["value"]):
                    continue
            latest[m] = r
    for m in sorted(latest):
        r = latest[m]
        dev = r.get("device", "?")
        flag = " [CPU-FALLBACK]" if r.get("cpu_fallback") else ""
        mfu = r.get("mfu_vs_v5e_peak")
        mfu_s = f"  mfu={mfu:.2%}" if isinstance(mfu, (int, float)) else ""
        print(f"  {m}: {r.get('value')} {r.get('unit', '')} "
              f"({r.get('config', r.get('combo', ''))}, {dev}){mfu_s}{flag}")

    bisect = [r for r in rows if r.get("metric") == "llama_bisect"]
    if bisect:
        # a partial row is only news when no full trajectory row for the
        # same tag landed later (the partial is banked BEFORE the
        # discriminator evals; the full row supersedes it); multiple
        # bisect passes append duplicate rows — display the LAST per
        # (probe, tag/D) key so the digest shows one line per probe
        full_tags = {r.get("tag") for r in bisect
                     if r.get("probe") == "trajectory"}
        last_by_key = {}
        for r in bisect:
            last_by_key[(r.get("probe"), r.get("tag"), r.get("D"))] = r
        display = [r for r in bisect
                   if id(r) in set(map(id, last_by_key.values()))
                   and not (r.get("probe") == "trajectory_partial"
                            and r.get("tag") in full_tags)]
        print(f"\n  llama_bisect: {len(bisect)} rows "
              f"({len(display)} distinct probes shown)")
        for r in display:
            probe = r.get("probe")
            if probe == "kernel_causality":
                if r.get("error"):
                    print(f"    kernel: ERROR {r['error']}")
                else:
                    print(f"    kernel D={r.get('D')}: err={r.get('err')} "
                          f"leak={r.get('leak')} "
                          f"{'OK' if r.get('ok') else 'FAIL'}")
            elif probe == "verdict":
                status = "complete" if r.get("complete") else "INCOMPLETE"
                print(f"    VERDICT ({status}): {r.get('branch')}")
            elif probe == "trajectory_partial":
                print(f"    traj-partial[{r.get('tag')}]: "
                      f"first={r.get('first')} last={r.get('last')} "
                      f"(discriminator evals did not land)")
            elif r.get("error"):
                print(f"    traj[{r.get('tag')}]: ERROR {r['error']}")
            else:
                print(f"    traj[{r.get('tag')}]: first={r.get('first')} "
                      f"last={r.get('last')} "
                      f"fresh={r.get('loss_fresh_batch')} "
                      f"swap={r.get('loss_swapped_labels')} "
                      f"leak={r.get('input_leak')}")
        # a full trajectory row supersedes its partial twin — note overlap
    else:
        print("\n  llama_bisect: NO ROWS (quarantine unresolved)")

    # merge summary rows per D: bench_flash checkpoints per-S fragments
    # as each S completes (plus legacy whole-run rows) — display the union
    merged = {}
    for r in rows:
        if r.get("metric") != "flash_ab_summary":
            continue
        d = merged.setdefault(r.get("D", 64), {})
        for s, entry in r.get("per_seq", {}).items():
            d[int(s)] = entry
    for D in sorted(merged):
        per_seq = merged[D]
        wins = sorted(s for s, e in per_seq.items() if e.get("pallas_wins"))
        print(f"\n  flash_ab_summary (D={D}): "
              f"min_seq={wins[0] if wins else None} "
              f"per_seq={json.dumps({str(s): per_seq[s] for s in sorted(per_seq)})[:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
