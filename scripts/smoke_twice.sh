#!/bin/bash
# chip_smoke.py twice against ONE compile-cache directory, cold then warm:
# the warm process must load every executable from the cache (no misses, no
# new entries, compile seconds collapse) and print the same assignment
# digests. Run from the repo root; arguments go to chip_smoke.py
# (e.g. --cpu-dry-run). Full logs land in chiprun_out/.
set -u
mkdir -p chiprun_out
if [ -z "${JAX_COMPILATION_CACHE_DIR:-}" ]; then
  # our own fixed sub-directory: emptied so the first run is really cold
  export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache/smoke-twice
  rm -rf "$JAX_COMPILATION_CACHE_DIR"
fi
echo "cache dir: $JAX_COMPILATION_CACHE_DIR"
for tag in cold warm; do
  python chip_smoke.py "$@" > chiprun_out/smoke_$tag.out 2> chiprun_out/smoke_$tag.err
  rc=$?
  echo "== $tag rc=$rc entries=$(ls "$JAX_COMPILATION_CACHE_DIR" 2>/dev/null | wc -l)"
  tail -c 3000 chiprun_out/smoke_$tag.err
  cut -c1-1200 chiprun_out/smoke_$tag.out
  if [ $rc -ne 0 ]; then exit $rc; fi
done
python - <<'PY'
import json, sys
def load(p):
    return {d["phase"]: d for d in map(json.loads, open(p)) if "phase" in d}
a, b = load("chiprun_out/smoke_cold.out"), load("chiprun_out/smoke_warm.out")
same = {ph: a[ph]["digest"] == b[ph]["digest"] for ph in a if "digest" in a[ph]}
print("digests equal:", same)
print("cold summary:", a["summary"])
print("warm summary:", b["summary"])
ok = (all(same.values()) and b["summary"]["cache_misses"] == 0
      and b["summary"]["compile_cache"]["entries"]
      == a["summary"]["compile_cache"]["entries"])
sys.exit(0 if ok else 1)
PY
