"""Mesh-sharded checkpointing (orbax-backed).

The reference's ModelSerializer writes ONE zip from one JVM
(util/ModelSerializer.java — configuration.json + coefficients.bin +
updaterState.bin), which `util/model_serializer.py` mirrors byte-format-
exactly for single-host parity. THIS module is the TPU-first scale path the
reference cannot express: parameters, updater state and model state are
saved AS SHARDED jax.Arrays — on a multi-host mesh every process writes
only its own shards (orbax coordinates the global commit), and restore
places each shard directly onto the devices of whatever sharding the
target network currently holds (replicated single-chip, ZeRO-partitioned
optimizer state, tensor-parallel splits — anything). No host ever
materializes the full parameter set, which is what makes
beyond-single-host-memory models checkpointable at all.

Usage:
    save_checkpoint(net, "/ckpts/step1000")      # all processes call
    net2 = MultiLayerNetwork(conf).init()        # same architecture
    pw = ParallelWrapper.Builder(net2)...build() # optional: shard first
    load_checkpoint(net2, "/ckpts/step1000")     # restores INTO the
                                                 # current sharding layout

The zip serializer remains the interchange format; this is the
training-scale format (resume-exact: counters, rng, updater state and the
device-resident loop state all round-trip).
"""
from __future__ import annotations

import os

import jax
import numpy as np


def _tree(net):
    """The checkpointable pytree: everything exact resume needs. The
    structure is FIXED (no optional keys) so a template built from any
    same-architecture net always matches the saved tree: when the device
    loop state doesn't exist yet, a structurally-identical placeholder is
    stored and `has_loop` records which it was."""
    import jax.numpy as jnp
    loop = net._loop
    return {
        "params": net._params,
        "updater_state": net._updater_state,
        "model_state": net._model_state,
        "rng": net._rng,
        "iteration_count": int(net.conf.iteration_count),
        "epoch_count": int(getattr(net.conf, "epoch_count", 0)),
        "has_loop": loop is not None,
        "loop": (loop if loop is not None
                 else {"iteration": jnp.asarray(0.0, jnp.float32),
                       "rng": net._rng}),
    }


def _serializable(tree):
    """Multi-host: host-local (fully-addressable) jax.Arrays — loop
    scalars, rng keys, anything not yet mesh-sharded — cannot be
    serialized as global arrays; they are identical on every process, so
    ship them as numpy (orbax writes replicated values from the primary).
    Global sharded arrays pass through untouched (per-process shard
    writes). Single-host: no-op."""
    if jax.process_count() == 1:
        return tree
    return jax.tree.map(
        lambda a: (np.asarray(a)
                   if isinstance(a, jax.Array) and a.is_fully_addressable
                   else a), tree)


def save_checkpoint(net, path, overwrite=True):
    """Save a network's full training state with per-process shard writes.
    On a multi-host mesh EVERY process must call this (orbax coordinates
    the commit); single-host it is an ordinary atomic checkpoint dir.
    `overwrite=True` (default) replaces an existing checkpoint at `path`
    (the fixed-path periodic-save pattern, matching ModelSerializer's
    overwrite semantics); False raises if the destination exists."""
    import orbax.checkpoint as ocp
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), _serializable(_tree(net)),
                   force=bool(overwrite))
        ckptr.wait_until_finished()


class ShardedCheckpointManager:
    """Step-numbered sharded checkpoints with retention — the
    CheckpointListener/CheckpointManager role over the mesh-sharded
    format: keep the last `keep_last` steps plus the best-scoring one,
    prune the rest.

    Layout: `<directory>/ckpt_<step>/` per checkpoint +
    `<directory>/manager.json` metadata (steps, scores, best). On a
    multi-host mesh every process calls `save` (per-process shard
    writes); metadata writes and pruning happen on process 0 only."""

    def __init__(self, directory, keep_last=3, mode="min"):
        if mode not in ("min", "max"):
            raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
        self.directory = os.path.abspath(directory)
        self.keep_last = max(1, int(keep_last))
        self.mode = mode
        os.makedirs(self.directory, exist_ok=True)
        self._meta_path = os.path.join(self.directory, "manager.json")
        self._meta = {"steps": [], "scores": {}}
        if os.path.exists(self._meta_path):
            import json
            with open(self._meta_path) as f:
                self._meta = json.load(f)
            # retention policy is PERSISTED and validated: resuming with a
            # different mode would invert best_step and prune the true
            # best checkpoint — fail loudly instead
            for key, mine in (("mode", self.mode),
                              ("keep_last", self.keep_last)):
                stored = self._meta.get(key)
                if stored is not None and stored != mine:
                    raise ValueError(
                        f"checkpoint dir was managed with {key}={stored!r}"
                        f"; refusing to resume with {key}={mine!r} (pass "
                        f"the original value)")

    def _path(self, step):
        return os.path.join(self.directory, f"ckpt_{int(step)}")

    def steps(self):
        return list(self._meta["steps"])

    def latest_step(self):
        """Newest checkpointed step, or None for an empty directory — the
        crash-resume probe (TrainingMaster/ParallelWrapper fast-forward
        past this many averaging rounds on a re-run)."""
        return self._meta["steps"][-1] if self._meta["steps"] else None

    def best_step(self):
        scores = {int(s): v for s, v in self._meta["scores"].items()
                  if v is not None}
        if not scores:
            return None
        if self.mode == "min":
            # latest wins ties: smaller score first, then larger step
            return min(scores, key=lambda s: (scores[s], -s))
        return max(scores, key=lambda s: (scores[s], s))

    def save(self, net, step, score=None):
        """Checkpoint `net` at `step` (optionally scored), then prune to
        the retention policy. Returns the checkpoint path.

        Crash-safety ordering: the checkpoint is committed first (orbax is
        atomic), then the metadata is REPLACED atomically, and only then
        are pruned directories deleted — a crash at any point leaves
        metadata that references only fully-committed checkpoints (at
        worst some orphan directories, swept on the next save)."""
        step = int(step)
        path = self._path(step)
        save_checkpoint(net, path)
        if step not in self._meta["steps"]:
            self._meta["steps"].append(step)
            self._meta["steps"].sort()
        if score is not None or str(step) not in self._meta["scores"]:
            # never erase a recorded score with a score-less re-save: the
            # former best must not silently become prunable
            self._meta["scores"][str(step)] = (None if score is None
                                               else float(score))
        stale = self._compute_prune()
        self._write_meta()
        if jax.process_index() == 0:
            import shutil
            for s in stale:
                shutil.rmtree(self._path(s), ignore_errors=True)
            self._sweep_orphans()
        return path

    def _compute_prune(self):
        """Drop out-of-policy steps from the metadata; return them (the
        directories are deleted AFTER the metadata write)."""
        keep = set(self._meta["steps"][-self.keep_last:])
        best = self.best_step()
        if best is not None:
            keep.add(best)
        stale = [s for s in self._meta["steps"] if s not in keep]
        for step in stale:
            self._meta["steps"].remove(step)
            self._meta["scores"].pop(str(step), None)
        return stale

    def _write_meta(self):
        if jax.process_index() != 0:
            return
        import json
        self._meta["mode"] = self.mode
        self._meta["keep_last"] = self.keep_last
        tmp = self._meta_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._meta, f)
        os.replace(tmp, self._meta_path)       # atomic on POSIX

    def _sweep_orphans(self):
        """Delete ckpt_<step> dirs the metadata no longer references
        (left by a crash between metadata write and deletion)."""
        import shutil
        live = {f"ckpt_{s}" for s in self._meta["steps"]}
        for name in os.listdir(self.directory):
            if (name.startswith("ckpt_") and name not in live
                    and name[len("ckpt_"):].isdigit()):
                shutil.rmtree(os.path.join(self.directory, name),
                              ignore_errors=True)

    def restore(self, net, step):
        return load_checkpoint(net, self._path(int(step)))

    def restore_latest(self, net):
        if not self._meta["steps"]:
            raise FileNotFoundError(f"no checkpoints under "
                                    f"{self.directory!r}")
        return self.restore(net, self._meta["steps"][-1])

    def restore_best(self, net):
        best = self.best_step()
        if best is None:
            raise FileNotFoundError("no SCORED checkpoints under "
                                    f"{self.directory!r}")
        return self.restore(net, best)


class RoundCheckpointer:
    """Per-averaging-round checkpoint + crash-resume gate — the ONE
    implementation of the resume protocol shared by
    `ParameterAveragingTrainingMaster` and `ParallelWrapper` (the round is
    the resume unit: master = one split; wrapper = one batch in allreduce
    mode / one k-group in k-step mode).

    With `directory=None` it is a pure round counter (checkpointing off).
    Otherwise: `maybe_resume(net)` — once per lifetime, and only into a
    never-trained net (iteration_count 0; a warm net is an in-process
    continuation, not a crash restart) — restores the newest checkpoint
    and records how many rounds it covers; `round_starts()` then gates
    those rounds off (the caller still consumes their batches so the data
    stream stays aligned); `round_done(net)` saves every `every` rounds.
    Re-running the same training command after a crash therefore resumes
    from the last completed averaging round with the exact rng/counters,
    making the result bit-comparable to an uninterrupted run."""

    def __init__(self, directory=None, every=1, keep_last=3, resume=True,
                 owner="trainer"):
        self.directory = None if directory is None else str(directory)
        self.every = max(1, int(every))
        self.keep_last = max(1, int(keep_last))
        self.resume = bool(resume)
        self.owner = owner
        self.round = 0           # rounds dispatched, monotonic for life
        self.resume_round = 0    # rounds covered by a restored checkpoint
        self._mgr = None
        self._checked = False

    def manager(self):
        if self.directory is None:
            return None
        if self._mgr is None:
            self._mgr = ShardedCheckpointManager(self.directory,
                                                 keep_last=self.keep_last)
        return self._mgr

    def maybe_resume(self, net):
        if self._checked:
            return
        self._checked = True
        mgr = self.manager()
        if mgr is None:
            return
        last = mgr.latest_step()
        if (not self.resume or last is None
                or net.conf.iteration_count != 0):
            return
        mgr.restore(net, last)
        self.resume_round = last
        import logging
        logging.getLogger(__name__).warning(
            "%s: resuming from checkpoint round %d under %s — "
            "fast-forwarding past the already-trained rounds of the "
            "re-run", self.owner, last, self.directory)

    def round_starts(self):
        """True when this round must actually run; False when a restored
        checkpoint already contains it."""
        r = self.round
        self.round += 1
        return r >= self.resume_round

    def round_done(self, net):
        mgr = self.manager()
        if mgr is None or self.round % self.every != 0:
            return
        score = getattr(net, "_score", None)
        mgr.save(net, self.round,
                 score=None if score is None else float(score))


class ShardedModelSaver:
    """Early-stopping saver SPI over the sharded format (reference
    earlystopping/saver/LocalFileModelSaver.java, which writes the zip).
    The sharded format is not self-describing (no embedded conf), so the
    saver takes `net_factory` — a zero-arg callable building the same
    architecture — for the restore side."""

    def __init__(self, directory, net_factory):
        self.directory = os.path.abspath(directory)
        self.net_factory = net_factory
        os.makedirs(self.directory, exist_ok=True)

    @property
    def best_path(self):
        return os.path.join(self.directory, "bestModel")

    @property
    def latest_path(self):
        return os.path.join(self.directory, "latestModel")

    def save_best_model(self, net, score):
        save_checkpoint(net, self.best_path)

    def save_latest_model(self, net, score):
        save_checkpoint(net, self.latest_path)

    def get_best_model(self):
        return load_checkpoint(self.net_factory(), self.best_path)

    def get_latest_model(self):
        return load_checkpoint(self.net_factory(), self.latest_path)

    saveBestModel = save_best_model
    getBestModel = get_best_model


def _check_restore_shapes(tpl, metadata):
    """Loud architecture check: orbax (0.7) silently restores the SAVED
    shape when the template disagrees, so a checkpoint restored into the
    wrong architecture would hand the net mis-shaped parameters that only
    blow up (or worse, silently mistrain) later. Compare every array leaf
    the template and the stored metadata share and fail with the full
    mismatch list instead."""
    def flat(tree):
        out = {}
        for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            shape = getattr(leaf, "shape", None)
            if shape is not None:
                out[jax.tree_util.keystr(kp)] = tuple(shape)
        return out
    want, saved = flat(tpl), flat(metadata)
    bad = sorted(k for k in want.keys() & saved.keys()
                 if want[k] != saved[k])
    if bad:
        detail = "; ".join(f"{k}: saved {saved[k]} vs net {want[k]}"
                           for k in bad[:8])
        raise ValueError(
            f"checkpoint does not match the target architecture "
            f"({len(bad)} mismatched arrays): {detail}")


def load_checkpoint(net, path):
    """Restore a checkpoint INTO `net`, placing every shard onto the
    sharding each array currently has (shard a fresh net first — e.g. via
    ParallelWrapper's ZeRO/TP layouts — and the restore lands distributed;
    leave it unsharded and the restore lands replicated/local). The
    architecture must match the saved one (same pytree structure/shapes) —
    a mismatch raises instead of silently restoring the saved shapes.
    Returns `net`."""
    import orbax.checkpoint as ocp
    net._ensure_init()

    multi = jax.process_count() > 1

    def abstract(a):
        if isinstance(a, jax.Array):
            if multi and a.is_fully_addressable:
                # saved as replicated numpy (see _serializable) — restore
                # the same way; the first jit call device-puts it
                return jax.ShapeDtypeStruct(a.shape, a.dtype)
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
        if isinstance(a, np.ndarray):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a
    tpl = jax.tree.map(abstract, _tree(net))
    with ocp.StandardCheckpointer() as ckptr:
        try:
            metadata = ckptr.metadata(os.path.abspath(path))
        except Exception:  # noqa: BLE001 — older layouts: let orbax decide
            metadata = None
        if metadata is not None:
            _check_restore_shapes(tpl, metadata)
        doc = ckptr.restore(os.path.abspath(path), tpl)
    net._params = doc["params"]
    net._updater_state = doc["updater_state"]
    net._model_state = doc["model_state"]
    net._rng = doc["rng"]
    net.conf.iteration_count = int(doc["iteration_count"])
    if hasattr(net.conf, "epoch_count"):
        net.conf.epoch_count = int(doc["epoch_count"])
    net._loop = doc["loop"] if doc["has_loop"] else None
    return net
