"""Medians and spreads (IQR / median by ``statistics.quantiles``) of the runs
that ``full_sets.sh`` recorded: ``python3 benchmarks/spread.py
chiprun_out/sets_<tag>.jsonl``."""

import json, statistics, sys
rows=[json.loads(l) for l in open(sys.argv[1])]
sets={}
for r in rows:
    if r["line"] is None: print("NO LINE", r); continue
    sets.setdefault(r["set"], []).append(r["line"])
def iqr(v):
    q=statistics.quantiles(v,n=4); return (q[2]-q[0])/statistics.median(v)
for s,lines in sets.items():
    print("set",s,"n",len(lines),"correct",[l["correct"] for l in lines],"attempted",[l["attempted"] for l in lines],"failed",[l["failed"] for l in lines])
    names=lines[0]["metrics"].keys()
    for n in names:
        v=[l["metrics"][n]["value"] for l in lines if n in l["metrics"]]
        if len(v)>=2:
            print("   %-28s median %-14.6g spread %.4f%%  min %.6g max %.6g" % (n, statistics.median(v), 100*iqr(v) if len(v)>=4 else float('nan'), min(v), max(v)))
    print("   compared", [ {k:(round(c["value"],4) if isinstance(c["value"],float) else c["value"]) for k,c in l["compared"].items()} for l in lines][:12])
    if s=="trace":
        for l in lines: print("   device", l["device"])
