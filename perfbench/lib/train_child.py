"""The training child: the process that holds the chip for one link of a
preempt -> resume chain.

It runs the program's own entry, ``train.train(get_args(argv))`` — the
``Trainer`` of ``training/loop.py``, its data loader, prefetcher, signal
path, exit handler and checkpoint manager — and edits no program file. What
the benchmark adds, it adds from here, around calls into the program:

- the cell's configuration registered in ``models.configs.PRESETS``, built
  by the cell's model family (``perfbench/families/<family>.py``);
- weights from the seed (``weights.make_param_tree``) inside the trainer's
  one jitted init, in place of the initialiser of the family's model class;
- a wrapper around the compiled step that opens and closes the measured
  window on this process's clock, and reads what ``correct`` compares;
- a wrapper around ``_consume`` that stamps each step's completion;
- two tables of the program's as plain data (``program_records.py``): its
  scope names beside a traced window's trace, and in ``window.json`` the
  change of every counter of its registry over the window.

It talks to the parent in lines ``PERFBENCH {json}`` on stdout and in files
in the work directory. Roles: ``first`` (fresh state, warm steps, window),
``resumed`` (restore, first step stamped, then train until signalled),
``last`` (restore, a fixed number of steps, then the plain reference and
the comparison, in this process, after the program's state is freed).
"""

import gc
import json
import os
import sys
import time
import zlib

T_PROC = time.time()


def say(ev: str, **kw) -> None:
    kw.update(ev=ev, t=time.time())
    sys.stdout.write("PERFBENCH " + json.dumps(kw) + "\n")
    sys.stdout.flush()


def main() -> None:
    with open(sys.argv[1]) as fh:
        spec = json.load(fh)
    sys.path.insert(0, spec["root"])
    sys.path.insert(0, os.path.dirname(spec["bench_dir"]))
    os.chdir(spec["root"])
    say("proc_start", t_proc=T_PROC)
    # everything that needs no chip first: a child started ahead of its
    # turn imports here, then waits for the parent's word (its predecessor
    # has exited) before it touches the backend
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import program_records
    from perfbench.lib import weights as W
    from perfbench.lib.result import memory_peak_bytes

    from fault_tolerant_llm_training_tpu.models import configs as mc
    from fault_tolerant_llm_training_tpu.training import loop as tl
    import train as program_entry
    from fault_tolerant_llm_training_tpu.utils.config import get_args
    from fault_tolerant_llm_training_tpu.utils.logging import init_logger

    d = W.dims_of(spec["config"])
    family = W.family_of(d)
    model_class = family.model_class()  # its module imports here, ahead
    say("imported")
    if spec.get("wait_go"):
        go = json.loads(sys.stdin.readline() or "{}")
        if not go.get("go"):
            raise SystemExit(4)  # the parent gave up on the chain
        spec["argv"] = go.get("argv", spec["argv"])
        say("go")

    platform = jax.devices()[0].platform
    if spec["require_tpu"] and (platform != "tpu"
                                or len(jax.devices()) < spec["chips"]):
        say("no_chip", platform=platform, count=len(jax.devices()))
        raise SystemExit(3)
    say("backend", platform=platform,
        kind=jax.devices()[0].device_kind, count=len(jax.devices()))

    mc.PRESETS[spec["preset_name"]] = family.preset(
        spec["config"], seq_len=spec["traffic"]["sequence_length"])

    role = spec["role"]
    seed_key = jax.random.PRNGKey(spec["seed"])
    dtype = jnp.bfloat16 if spec["traffic"].get(
        "model_dtype", "bf16") == "bf16" else jnp.float32
    fault = spec.get("fault", "")

    # ---- weights from the seed, inside the trainer's jitted init ---------
    orig_model_init = model_class.init

    def seeded_init(self, key, *a, **k):
        want = jax.eval_shape(lambda kk: orig_model_init(self, kk, *a, **k),
                              key)["params"]
        mine = W.make_param_tree(seed_key, d, dtype)
        ws, ms = (jax.tree_util.tree_structure(want),
                  jax.tree_util.tree_structure(mine))
        assert ws == ms, f"param tree differs:\n{ws}\n{ms}"
        for a_, b_ in zip(jax.tree_util.tree_leaves(want),
                          jax.tree_util.tree_leaves(mine)):
            assert a_.shape == b_.shape and a_.dtype == b_.dtype, (a_, b_)
        return {"params": mine}

    model_class.init = seeded_init

    # ---- small jitted readers -------------------------------------------
    def _norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(tree)]

    leaf_norms = jax.jit(_norms)

    def _digest(tree):
        out = []
        for x in jax.tree_util.tree_leaves(tree):
            bits = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
            u = jax.lax.bitcast_convert_type(x, bits).astype(
                jnp.uint32).reshape(-1)
            idx = jax.lax.iota(jnp.uint32, u.shape[0]) % 65521 + 1
            out.append(jnp.stack([jnp.sum(u), jnp.sum(u * idx)]))
        return jnp.stack(out)

    state_digest = jax.jit(_digest)

    def digest_of(state) -> str:
        arr = np.asarray(state_digest(state))
        return f"{zlib.crc32(arr.tobytes()):08x}"

    def flat_paths(params) -> list:
        """Leaf paths in ``tree_leaves`` order."""
        return ["/".join(str(getattr(k, "key", k)) for k in path)
                for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]

    def change_norms(params) -> dict:
        """Per leaf ||p - p0||, p0 made again from the seed, leaf by leaf."""
        flat = W.flatten(params)
        leaves = W.all_leaves(d)
        out = {}

        @jax.jit
        def one(p, p0):
            return jnp.sqrt(jnp.sum(jnp.square(
                p.astype(jnp.float32) - p0.astype(jnp.float32))))

        for path, p in flat.items():
            shape, kind = leaves[path]
            p0 = W.make_leaf(seed_key, path, shape, kind, dtype,
                             d["family"])
            out[path] = float(one(p, p0))
            del p0
        return out

    def adam_mu(opt_state):
        hits = [s for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu"))
            if hasattr(s, "mu")]
        assert len(hits) == 1, "one Adam state expected"
        return hits[0].mu

    work = spec["work_dir"]
    seconds = float(spec["seconds"])
    warm_steps = int(spec["traffic"]["warm_steps"])
    tokens_per_step = (spec["traffic"]["sequence_length"]
                       * spec["batch_size"])
    box = {"calls": 0, "t_open": None, "closed": False, "done_t": [],
           "readings": {}, "trace_on": False, "trainer": None}

    # ---- the hooks --------------------------------------------------------
    orig_trainer_init = tl.Trainer.__init__
    orig_save = tl.Trainer.save_checkpoint
    orig_close = tl.Trainer.close

    def trainer_init(self, *a, **k):
        orig_trainer_init(self, *a, **k)
        box["trainer"] = self
        say("trainer_ready", step=int(self.training_step))
        if role != "first":
            t_dg = time.time()
            dg = digest_of(self.state)
            say("restored", step=int(self.training_step), digest=dg,
                data_state=self._last_data_state,
                digest_s=time.time() - t_dg)
        inner_step = self._compiled_step
        inner_consume = self._consume

        def step(state, inputs, labels):
            n = box["calls"]
            box["calls"] += 1
            first_of_proc = n == 0
            if first_of_proc or (role == "first" and n < warm_steps):
                crc = zlib.crc32(np.asarray(inputs).tobytes())
                say("batch", call=n, step=int(self.training_step),
                    crc=f"{crc:08x}")
            if role == "first" and n == warm_steps:
                jax.block_until_ready(state)
                if spec["trace"]:
                    os.makedirs(spec["trace_dir"], exist_ok=True)
                    program_records.write_scopes(work)
                    jax.profiler.start_trace(spec["trace_dir"])
                    box["trace_on"] = True
                    box["t_trace0"] = time.perf_counter()
                box["counters0"] = program_records.counters()
                box["t_open"] = time.perf_counter()
                box["stall0"] = stall_total()
                say("window_open", step=int(self.training_step))
            if (role == "first" and box["t_open"] is not None
                    and box["trace_on"]
                    and n - warm_steps >= spec["trace_steps"]):
                jax.block_until_ready(state)
                box["trace_window_s"] = time.perf_counter() - box["t_trace0"]
                box["traced_steps"] = n - warm_steps
                jax.profiler.stop_trace()
                box["trace_on"] = False
            if (role == "first" and box["t_open"] is not None
                    and not box["closed"]
                    and time.perf_counter() - box["t_open"] >= seconds
                    and n > warm_steps):
                jax.block_until_ready(state)
                t_close = time.perf_counter()
                box["closed"] = True
                steps = n - warm_steps
                out = {
                    "steps": steps, "tokens": steps * tokens_per_step,
                    "window_s": t_close - box["t_open"],
                    "memory_peak_bytes": memory_peak_bytes(),
                    "data_stall_s": stall_total() - box["stall0"],
                    "counters": program_records.change(
                        box["counters0"], program_records.counters()),
                    "done_t": box["done_t"],
                    "trace_window_s": box.get("trace_window_s"),
                    "traced_steps": box.get("traced_steps"),
                    "readings": box["readings"],
                    "first_step": warm_steps,
                }
                with open(os.path.join(work, "window.json"), "w") as fh:
                    json.dump(out, fh)
                say("window_closed", steps=steps)
            if fault == "state_unchanged":
                keep = jax.tree_util.tree_map(jnp.copy, state)
                _, metrics = inner_step(state, inputs, labels)
                new_state = keep
            elif fault == "half_batch":
                half = max(1, inputs.shape[0] // 2)
                reps = -(-inputs.shape[0] // half)
                new_state, metrics = inner_step(
                    state,
                    jnp.concatenate([inputs[:half]] * reps)[:inputs.shape[0]],
                    jnp.concatenate([labels[:half]] * reps)[:labels.shape[0]])
            else:
                new_state, metrics = inner_step(state, inputs, labels)
            if role == "first" and n < warm_steps:
                vals = np.asarray(metrics["packed"])
                r = box["readings"]
                r.setdefault("loss", []).append(float(vals[0]))
                r.setdefault("grad_norm_raw", []).append(float(vals[1]))
                if n == 0:
                    mu = adam_mu(new_state.opt_state)
                    norms = np.asarray(leaf_norms(mu)) / (1.0 - 0.9)
                    r["grad_norms"] = dict(zip(
                        flat_paths(new_state.params),
                        [float(x) for x in norms]))
                if n == warm_steps - 1:
                    r["change_norms"] = change_norms(new_state.params)
            if first_of_proc and role != "first":
                jax.block_until_ready(metrics["packed"])
                say("first_step_done", step=int(self.training_step),
                    loss=float(np.asarray(metrics["packed"])[0]))
            return new_state, metrics

        def consume(step_no, packed):
            inner_consume(step_no, packed)
            if box["t_open"] is not None and not box["closed"]:
                box["done_t"].append(time.perf_counter())

        self._compiled_step = step
        self._consume = consume

    def stall_total() -> float:
        return float(box["trainer"]._m_stall.value)

    def save_checkpoint(self, *a, **k):
        t_dg = time.time()
        dg = digest_of(self.state)
        digest_s = time.time() - t_dg
        step = orig_save(self, *a, **k)
        say("saved", step=int(step), digest=dg, digest_s=digest_s,
            data_state=self._last_data_state, calls=box["calls"])
        return step

    def close(self):
        orig_close(self)
        self.state = None
        self._compiled_step = None
        self._jit_step = None

    tl.Trainer.__init__ = trainer_init
    tl.Trainer.save_checkpoint = save_checkpoint
    tl.Trainer.close = close

    init_logger()
    cfg = get_args(spec["argv"])
    try:
        program_entry.train(cfg)
    except SystemExit as e:
        if e.code not in (0, None):
            raise
    say("program_done", calls=box["calls"])

    if spec.get("compare"):
        box["trainer"] = None
        gc.collect()
        for arr in jax.live_arrays():
            arr.delete()
        jax.clear_caches()
        gc.collect()
        from perfbench.lib import train_compare

        t0 = time.time()
        result = train_compare.run(spec, d)
        result["reference_s"] = time.time() - t0
        with open(os.path.join(work, "compare.json"), "w") as fh:
            json.dump(result, fh)
        say("compared", seconds=result["reference_s"])
    sys.stdout.flush()


if __name__ == "__main__":
    main()
