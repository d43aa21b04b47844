# What a bound is set from (the builder's contract): two sets of 6 runs of one
# cell on the same 6 seeds, then 3 traced runs on 3 more, one process each.
# usage (on the chip): bash benchmarks/full_sets.sh <workload> <seconds> <tag> [<out dir>]
# Appends a line a run to <out dir>/sets_<tag>.jsonl (chiprun_out/ unless
# given: a checkout unpacked inside the repo writes to ../chiprun_out);
# benchmarks/spread.py reads it.
W=$1; S=$2; T=$3; O=${4:-chiprun_out}
mkdir -p $O
for set in 1 2; do
  for seed in 2147492123 2147493131 2147494157 2147495167 2147496173 2147497201; do
    python3 benchmarks/run.py --workload $W --seed $seed --seconds $S --trace 0 > $O/.one.out 2> $O/.one.err
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $?, \"line\": $(tail -n 1 $O/.one.out | grep '^{' || echo null)}" >> $O/sets_${T}.jsonl
    (grep -h phase $O/.one.out; tail -n 6 $O/.one.err | grep -v Warn) >> $O/sets_${T}.err
  done
done
for seed in 2147498207 2147499223 2147500231; do
  python3 benchmarks/run.py --workload $W --seed $seed --seconds $S --trace 1 > $O/.one.out 2> $O/.one.err
  echo "{\"set\": \"trace\", \"seed\": $seed, \"rc\": $?, \"line\": $(tail -n 1 $O/.one.out | grep '^{' || echo null)}" >> $O/sets_${T}.jsonl
  (grep -h phase $O/.one.out; tail -n 6 $O/.one.err | grep -v Warn) >> $O/sets_${T}.err
done
tail -n 3 $O/sets_${T}.jsonl | cut -c1-1500
