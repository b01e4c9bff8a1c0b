#!/bin/bash
# Same-seed pairs of benchmark cells, parent against change, inside ONE chip call
# (the two sides of a comparison share a chip and a seed). Before the call, unpack both trees
# into a git-ignored directory of the repo:
#   git archive <parent> | tar -x -C .chip_checkout/parent     (lay new benchmark files over it)
#   git archive $(git write-tree) | tar -x -C .chip_checkout/final
# usage: chiprun -- bash tools/chip_pairs.sh <tag> <cell>:<seed>[:<seed>...] ...
#   per cell, a pair a seed, the sides in alternating order: parent, change; change, parent; ...
#   RUN_SECONDS (default 45) is each run's window.
# One line a run goes to chiprun_out/<tag>.jsonl (side, cell, seed, correct, failed, the
# end-to-end metrics, memory_peak_bytes); every line run.py printed (layer_means_ms, host,
# phases_s: what tells a slow process from a slow path) to chiprun_out/<tag>.<side>.full.jsonl.
# Every path is the checkout's own: the root is where this file lies, a run's last line goes
# through a file of its own under $TMPDIR.
root=$(cd "$(dirname "$0")/.." && pwd)
tag=$1; shift
out=$root/chiprun_out; mkdir -p "$out"; : > "$out/$tag.jsonl"
run() {  # side cell seed
  local line; line=$(mktemp)
  (cd "$root/.chip_checkout/$1" && python3 benchmark/run.py --workload "$2" --seed "$3" \
    --seconds "${RUN_SECONDS:-45}" --trace 0 2>> "$out/$tag.err") \
    | tee -a "$out/$tag.$1.full.jsonl" | tail -n 1 > "$line"
  python3 - "$1" "$2" "$3" "$line" <<'PY' | tee -a "$out/$tag.jsonl"
import json, sys
side, cell, seed, line = sys.argv[1:5]
try:
    r = json.load(open(line))
    m = {k: v["value"] for k, v in r.get("metrics", {}).items()}
    print(json.dumps({"side": side, "cell": cell, "seed": int(seed), "correct": r.get("correct"), "failed": r.get("failed"), **m, "peak": r.get("device", {}).get("memory_peak_bytes")}))
except Exception as e:
    print(json.dumps({"side": side, "cell": cell, "seed": int(seed), "error": str(e)}))
PY
  rm -f "$line"
}
for spec in "$@"; do
  IFS=: read -r cell seeds <<< "$spec"
  first=parent; second=final
  for seed in ${seeds//:/ }; do
    run $first "$cell" "$seed"; run $second "$cell" "$seed"
    swap=$first; first=$second; second=$swap
  done
done
