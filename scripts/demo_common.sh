# Shared setup for the fault-chain demos (sourced by
# demo_fault_chain.sh and demo_sbatch_chain.sh): CPU-only JAX env with
# the compile cache, plus a synthetic-parquet generator. Keeping this in
# one file stops the two demos' environments from drifting.

demo_cpu_env() {
    export JAX_PLATFORMS=cpu
    export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/tmp/jax_test_compile_cache}
    export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
}

# demo_make_parquet <output-path>
demo_make_parquet() {
    python - "$1" <<'EOF'
import sys
import numpy as np, pyarrow as pa, pyarrow.parquet as pq
rng = np.random.default_rng(0)
words = ['alpha','bravo','charlie','delta','echo','foxtrot']
docs = [' '.join(rng.choice(words, size=int(rng.integers(20,200)))) for _ in range(256)]
pq.write_table(pa.table({'text': docs}), sys.argv[1])
EOF
}
