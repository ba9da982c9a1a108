# Two sets of runs of one cell, the same seeds in both, each result in
# its own file: the spreads that a bound is set from.
#   bash bench/sets.sh <cell> <outdir> <seconds> <seed>...
c=$1; out=$2; secs=$3; shift 3
mkdir -p "$out"
for set in A B; do
  for s in "$@"; do
    timeout 600 python3 bench/run.py --workload "$c" --seed "$s" --seconds "$secs" --trace 0 > "$out/$c.$set.$s.out" 2> "$out/$c.$set.$s.err"
    echo "$c set $set seed $s rc=$? $(tail -n1 "$out/$c.$set.$s.out" | cut -c1-400)"
  done
done
