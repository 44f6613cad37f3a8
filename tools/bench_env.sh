#!/usr/bin/env bash
# Benchmark environment pinning (DESIGN.md §10.4): source this — or run a
# command through it — before any `python -m benchmarks.run` invocation so
# the numbers that land in BENCH_*.json are produced under one declared
# allocator/topology/cache regime instead of whatever the shell happened to
# have.  Usage:
#
#     source tools/bench_env.sh                       # pin this shell
#     tools/bench_env.sh python -m benchmarks.run sweep   # pin one command
#
# Everything here is override-friendly: a variable already set in the
# environment wins.

# 1) tcmalloc: glibc malloc's arena churn adds multi-percent noise to the
#    short-lived buffers of the interpret-mode Pallas paths.  Preload
#    tcmalloc when the box has it; SKIP silently when it doesn't (this
#    container does not bake it in) — benchmarks must run identically, just
#    noisier, without it.
if [ -z "${LD_PRELOAD:-}" ]; then
    for _tc in /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
               /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
               /usr/lib/libtcmalloc_minimal.so; do
        if [ -e "${_tc}" ]; then
            export LD_PRELOAD="${_tc}"
            break
        fi
    done
    unset _tc
fi

# 2) Host-device topology: the batch/transport suites shard over host
#    devices; pin the count so BENCH_batch.json is comparable across runs
#    (suites that fork workers override per-process, as CI does).  Default
#    to the core count: forcing more host devices than cores visibly slows
#    the single-device suites (measured ~2x on sweep_engines at 8 devices
#    on a 1-core box — the device framework fans work out with no cores to
#    catch it).
if [ -z "${XLA_FLAGS:-}" ]; then
    _nd="${REPRO_BENCH_DEVICES:-$(nproc 2>/dev/null || echo 1)}"
    export XLA_FLAGS="--xla_force_host_platform_device_count=${_nd}"
    unset _nd
fi

# 3) Persistent compilation cache: first-call numbers in a fresh process
#    otherwise include XLA compile time; a warm on-disk cache makes the
#    warmup call cheap and keeps the timed region pure execute.  The cache
#    key includes its directory, so it defaults to the checkout's fixed
#    .jax_cache/ (the same place chip_smoke.py and benchmarks/run.py use).
#    JAX only writes entries over ~1s compile time by default; threshold 0
#    caches everything the benchmarks build.
_repo="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-${_repo}/.jax_cache}"
unset _repo
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="${JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS:-0}"
mkdir -p "${JAX_COMPILATION_CACHE_DIR}"

# Exec mode: `tools/bench_env.sh cmd args...` runs cmd under the pinned env.
if [ "$#" -gt 0 ]; then
    exec "$@"
fi
