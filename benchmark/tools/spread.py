"""Spread of the sets that tools/sets.sh left under chiprun_out/<cell>/: for each
metric and set the median and the quartile distance over the median
(statistics.quantiles, n=4), and the second median against the first.

    python3 benchmark/tools/spread.py <cell>
"""
import glob, json, statistics, sys
cell = sys.argv[1]
sets = {1: {}, 2: {}}
for path in sorted(glob.glob(f"chiprun_out/{cell}/set*_*.log")):
    s = int(path.split("/set")[1][0]); seed = path.split("_")[-1][:-4]
    last = open(path).read().strip().splitlines()[-1]
    try:
        line = json.loads(last)
    except Exception:
        print("NO RESULT", path, last[:200]); continue
    for k, v in line["metrics"].items():
        sets[s].setdefault(k, []).append((seed, v["value"]))
    sets[s].setdefault("_correct", []).append((seed, line["correct"]))
    sets[s].setdefault("_mem", []).append((seed, line["device"]["memory_peak_bytes"]))
    sets[s].setdefault("_attempted", []).append((seed, line["attempted"]))
for k in sorted(set(sets[1]) | set(sets[2])):
    if k.startswith("_"):
        print(k, {s: [v for _, v in sets[s].get(k, [])] for s in sets}); continue
    out = []
    for s in (1, 2):
        vals = [v for _, v in sets[s].get(k, [])]
        if len(vals) >= 2:
            q = statistics.quantiles(vals, n=4); med = statistics.median(vals)
            out.append((s, len(vals), med, (q[2]-q[0])/med, min(vals), max(vals)))
    print(k)
    for o in out: print("   set %d n=%d median %.4f spread %.4f%% min %.4f max %.4f" % (o[0], o[1], o[2], 100*o[3], o[4], o[5]))
    if len(out) == 2: print("   second median vs first: %+.3f%%" % (100*(out[1][2]/out[0][2]-1)))
