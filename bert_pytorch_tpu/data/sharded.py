"""Streaming sharded-HDF5 pretraining input pipeline.

Reads the same container format as the reference's offline pipeline
(gzip'd HDF5 with keys input_ids / special_token_positions /
next_sentence_labels, written by utils/encode_data.py:204-210; legacy
NVIDIA premasked files with segment_ids/input_mask/masked_lm_* also accepted,
src/dataset.py:183-192), but the runtime design is different:

- **Batch-granular, not sample-granular.** The reference served one sample per
  __getitem__ through a forked DataLoader worker; on TPU-VM the host feeds a
  whole per-host batch per step, so the loader slices contiguous batches
  straight out of the in-RAM shard and masks them vectorized
  (data/masking.py). No worker processes, no per-sample Python.
- **Futures, not bare threads.** The reference handed the prefetched shard
  over via an attribute written by a raw thread with no lock
  (src/dataset.py:210-222, SURVEY §5.2); here a ThreadPoolExecutor future
  carries the result — exceptions propagate and the handoff is synchronized.
- **Per-host contiguous chunking.** Same index math as the reference's custom
  DistributedSampler (src/dataset.py:341-399): the global index space is
  padded to world_size * num_samples and each host takes a contiguous chunk so
  hosts stream different files; the cursor is checkpointable and restores
  mid-epoch (src/dataset.py:401-425 semantics, incl. skip-with-warning when
  world size or dataset size changed).
- **Optional sequence packing** (``packing=True``): each batch row is
  assembled from multiple short examples by the greedy first-fit packer in
  data/packing.py, with block-diagonal ``segment_ids`` / per-segment
  ``position_ids`` / per-segment NSP fields. The packer's carry-over buffer
  is checkpointed as a list of global sample indices alongside the sampler
  cursor, so resume replays the identical bin layout.
"""

from __future__ import annotations

import bisect
import logging
import warnings
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from bert_pytorch_tpu.data import masking

logger = logging.getLogger(__name__)

REQUIRED_KEYS = ("input_ids", "next_sentence_labels")


class ShardIndex:
    """Discover + verify shard files and map global sample idx -> (file, row).

    Mirrors the reference's _verify_and_count_samples behavior
    (src/dataset.py:298-338): unreadable files or files whose per-key counts
    disagree are skipped with a warning, not fatal.
    """

    def __init__(self, files: Sequence[str]):
        import h5py

        files = sorted(str(f) for f in files)
        self.files: List[str] = []
        self.starts: List[int] = []  # cumulative start index per file
        # widest masked_lm_positions row across legacy premasked shards
        # (None = all shards are dynamic-masking); reading .shape is free
        self.premasked_width: Optional[int] = None
        total = 0
        for path in files:
            try:
                with h5py.File(path, "r") as f:
                    counts = {len(f[k]) for k in REQUIRED_KEYS}
                    width = None
                    if "masked_lm_positions" in f:
                        shape = f["masked_lm_positions"].shape
                        if len(shape) != 2:
                            warnings.warn(
                                f"skipping shard {path}: masked_lm_positions "
                                f"has shape {shape}, expected 2-D")
                            continue
                        width = int(shape[1])
            except (OSError, KeyError) as e:
                warnings.warn(f"skipping unreadable shard {path}: {e}")
                continue
            if len(counts) != 1:
                warnings.warn(f"skipping shard {path}: per-key sample counts differ")
                continue
            # only shards actually kept contribute to the premasked width
            if width is not None:
                self.premasked_width = max(self.premasked_width or 0, width)
            self.files.append(path)
            self.starts.append(total)
            total += counts.pop()
        if not self.files:
            raise RuntimeError("no valid shard files found")
        self.total = total

    def __len__(self) -> int:
        return self.total

    def locate(self, idx: int) -> Tuple[int, int]:
        """global sample idx -> (file_idx, row_within_file)."""
        if not 0 <= idx < self.total:
            raise IndexError(f"sample {idx} out of range ({self.total})")
        fi = bisect.bisect_right(self.starts, idx) - 1
        return fi, idx - self.starts[fi]

    def file_range(self, fi: int) -> Tuple[int, int]:
        start = self.starts[fi]
        end = self.starts[fi + 1] if fi + 1 < len(self.files) else self.total
        return start, end


def _load_shard(path: str) -> Dict[str, np.ndarray]:
    import h5py

    with h5py.File(path, "r") as f:
        return {k: np.asarray(f[k][:]) for k in f.keys()}


class HostShardSampler:
    """Resumable contiguous per-host index stream.

    Global index space padded (by wraparound) to world_size * num_samples;
    host r owns [r * num_samples, (r+1) * num_samples). state_dict/
    load_state_dict carry the cursor for mid-epoch resume with the same
    compatibility guards as the reference (src/dataset.py:401-425).
    """

    def __init__(self, dataset_size: int, world_size: int = 1, rank: int = 0,
                 seed: int = 0):
        if not 0 <= rank < world_size:
            raise ValueError(f"rank {rank} out of range for world {world_size}")
        self.dataset_size = dataset_size
        self.world_size = world_size
        self.rank = rank
        self.seed = seed
        self.num_samples = -(-dataset_size // world_size)  # ceil
        self.total_size = self.num_samples * self.world_size
        self.index = 0  # position within this host's chunk
        self.epoch = 0

    def __len__(self) -> int:
        return self.num_samples

    def next_indices(self, n: int) -> Optional[np.ndarray]:
        """Next n global sample indices for this host, or None at epoch end
        (partial tail batches are dropped — static shapes for jit)."""
        if self.index + n > self.num_samples:
            return None
        base = self.rank * self.num_samples + self.index
        out = (np.arange(base, base + n) % self.dataset_size)
        self.index += n
        return out

    def reset_epoch(self) -> None:
        self.index = 0
        self.epoch += 1

    def state_dict(self) -> Dict[str, int]:
        return {
            "epoch": self.epoch,
            "seed": self.seed,
            "world_size": self.world_size,
            "total_size": self.total_size,
            "index": self.index,
        }

    def load_state_dict(self, state: Dict[str, int]) -> None:
        if state.get("total_size") != self.total_size:
            warnings.warn(
                "sampler total_size changed "
                f"({state.get('total_size')} -> {self.total_size}); "
                "not restoring sampler state")
            return
        if state.get("world_size") != self.world_size:
            warnings.warn("world size changed; not restoring sampler state")
            return
        self.epoch = state["epoch"]
        self.seed = state["seed"]
        self.index = state["index"]


# what a batch holds under objective="clm"
CLM_FIELDS = ("input_ids", "attention_mask", "segment_ids", "position_ids")


class PretrainingDataLoader:
    """Iterator of ready-to-device batches with background shard prefetch.

    Yields dicts of numpy arrays shaped (batch, seq):
      input_ids, token_type_ids, attention_mask, masked_lm_labels  (+
      next_sentence_labels (batch,)).

    Dynamic-masking mode applies when shards carry special_token_positions;
    legacy premasked shards are served as-is with dense labels. One shard is
    resident while the next loads on an executor thread — same ≤2-files-in-RAM
    budget as the reference (src/dataset.py docstring), minus the forked
    DataLoader workers.

    prefetch_batches > 0 moves batch assembly (row gather + dynamic masking)
    onto a dedicated executor thread with that many batches in flight, so
    batch N+1 is guaranteed — not incidentally — prepared while the device
    runs batch N (the reference's 4 DataLoader workers served the same
    purpose, run_pretraining.py:384). state_dict() then reports the sampler
    cursor as of the last batch actually YIELDED, not the last one
    assembled ahead, so checkpoint resume replays nothing and skips nothing.
    """

    def __init__(
        self,
        index: ShardIndex,
        sampler: HostShardSampler,
        batch_size: int,
        mask_token_index: Optional[int],
        max_pred_per_seq: int,
        masked_lm_prob: float,
        vocab_size: int,
        original_token_prob: float = 0.1,
        random_token_prob: float = 0.1,
        seed: Optional[int] = None,
        prefetch_batches: int = 0,
        packing: bool = False,
        packing_max_segments: int = 8,
        packing_lookahead: int = 4,
        batch_tap=None,
        objective: str = "mlm",
    ):
        if objective not in ("mlm", "clm"):
            raise ValueError(f"unknown objective {objective!r}")
        # "clm" (causal language modelling, the decoder families): nothing is
        # masked and no label is made (the next token is the label); a batch
        # is input_ids, attention_mask and, packed, segment_ids and
        # position_ids
        self.objective = objective
        if not 0 <= masked_lm_prob <= 1:
            raise ValueError("masked_lm_prob must be in [0,1]")
        if original_token_prob + random_token_prob > 1:
            raise ValueError("original_token_prob + random_token_prob > 1")
        if max_pred_per_seq < 0:
            raise ValueError("max_pred_per_seq must be >= 0")
        if (index.premasked_width is not None
                and index.premasked_width > max_pred_per_seq):
            # the gathered MLM head scores only max_pred_per_seq positions per
            # row; wider premasked shards would silently lose supervision
            raise ValueError(
                f"premasked shards carry up to {index.premasked_width} masked "
                f"positions per row but max_pred_per_seq={max_pred_per_seq}; "
                "raise --max_predictions_per_seq to at least the shard width "
                "or re-encode the data")
        self.index = index
        self.sampler = sampler
        self.batch_size = batch_size
        self.mask_token_index = mask_token_index
        self.max_pred_per_seq = max_pred_per_seq
        self.masked_lm_prob = masked_lm_prob
        self.vocab_size = vocab_size
        self.original_token_prob = original_token_prob
        self.random_token_prob = random_token_prob
        # masking rng seed: masks are a PURE FUNCTION of
        # (seed, epoch, global sample index) — per-example derivation in
        # _build_examples, the same contract the streaming plane pinned in
        # round 16 (data/streaming.py _example_rng). A resumed run (or the
        # packer rebuilding its carry-over buffer from checkpointed
        # indices) therefore re-derives BIT-identical masks, which is what
        # makes the round-17 survival drill's bit-identity hold on this
        # plane; masks still refresh every epoch (sampler.epoch feeds the
        # derivation). The pre-round-17 single stateful rng advanced with
        # consumption history, so resume replayed different masks.
        self._mask_seed = int(seed if seed is not None else sampler.seed)
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="shard-prefetch")
        self._resident_fi: Optional[int] = None
        self._resident: Optional[Dict[str, np.ndarray]] = None
        self._pending_fi: Optional[int] = None
        self._pending: Optional[Future] = None
        # batch-assembly prefetch: a SEPARATE single-worker executor (the
        # shard pool must stay free — _ensure_resident blocks on it, and
        # sharing one worker would deadlock). Only the assembler thread
        # touches sampler/rng/shard residency once prefetching starts.
        self.prefetch_batches = int(prefetch_batches)
        self._assembler: Optional[ThreadPoolExecutor] = None
        self._queue: List[Future] = []
        # sequence packing (data/packing.py): batch rows assembled from
        # multiple short examples; _pending holds global sample indices
        # fetched but not yet placed in a row (checkpointed for resume)
        self.packing = bool(packing)
        if self.packing and packing_max_segments < 1:
            raise ValueError("packing_max_segments must be >= 1")
        self.packing_max_segments = int(packing_max_segments)
        self.packing_lookahead = max(1, int(packing_lookahead))
        self._pending_examples: List[int] = []
        # built (gathered + masked) rows aligned with _pending_examples, so
        # a carried-over example is masked ONCE when fetched, not re-gathered
        # and re-masked on every batch it waits through (~lookahead x host
        # cost otherwise). None = rebuild lazily from the indices (the state
        # restored from a checkpoint carries indices only).
        self._pending_built: Optional[Dict[str, np.ndarray]] = None
        # batch_tap(batch) fires for every batch this loader YIELDS, on the
        # consumer thread — the flight recorder's capture point at the
        # loader boundary (telemetry/flight_recorder.py). Because it runs
        # at yield (not at assembly), tap order equals consumption order
        # even with the prefetch executor running ahead. Assignable after
        # construction too (run_pretraining attaches it post-peek).
        self.batch_tap = batch_tap
        self._closed = False
        self._last_state = self._state_snapshot()
        if self.prefetch_batches > 0:
            self._assembler = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="batch-assemble")

    # -- shard residency ----------------------------------------------------

    def _ensure_resident(self, fi: int) -> Dict[str, np.ndarray]:
        if fi == self._resident_fi:
            return self._resident
        if fi == self._pending_fi and self._pending is not None:
            self._resident = self._pending.result()
            self._resident_fi = fi
        else:
            self._resident = _load_shard(self.index.files[fi])
            self._resident_fi = fi
        # queue the host's next file
        nxt = (fi + 1) % len(self.index.files)
        self._pending_fi = nxt
        self._pending = self._pool.submit(_load_shard, self.index.files[nxt])
        return self._resident

    # -- batch assembly -----------------------------------------------------

    def _gather_rows(self, indices: np.ndarray) -> Dict[str, np.ndarray]:
        """Gather rows for (sorted, mostly-contiguous) global indices; may
        span a shard boundary, in which case the next shard becomes resident."""
        out: Dict[str, List[np.ndarray]] = {}
        i = 0
        while i < len(indices):
            fi, row = self.index.locate(int(indices[i]))
            data = self._ensure_resident(fi)
            _, file_end = self.index.file_range(fi)
            # rows from this file: run of indices < file_end
            j = i
            while j < len(indices) and int(indices[j]) < file_end \
                    and int(indices[j]) >= self.index.starts[fi]:
                j += 1
            rows = np.asarray(indices[i:j]) - self.index.starts[fi]
            for k, arr in data.items():
                out.setdefault(k, []).append(arr[rows])
            i = j
        return {k: np.concatenate(v, axis=0) for k, v in out.items()}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        if self._assembler is not None:
            # pop BEFORE topping up: the batch being waited on does not count
            # against the lookahead, so prefetch_batches=1 still overlaps
            # one assembly with the device step
            if not self._queue:
                self._queue.append(self._assembler.submit(self._assemble_one))
            head = self._queue.pop(0)
            while len(self._queue) < self.prefetch_batches:
                self._queue.append(self._assembler.submit(self._assemble_one))
            batch, state = head.result()
            if batch is None:  # epoch end: drain queued end-markers
                self._drain_queue()
                raise StopIteration
            self._last_state = state
            if self.batch_tap is not None:
                self.batch_tap(batch)
            return batch
        batch = self._assemble_sync()
        if batch is None:
            raise StopIteration
        self._last_state = self._state_snapshot()
        if self.batch_tap is not None:
            self.batch_tap(batch)
        return batch

    def _assemble_one(self):
        """Assembler-thread task: (batch, loader_state_after) or (None, _)
        at epoch end."""
        batch = self._assemble_sync()
        return batch, self._state_snapshot()

    def _assemble_sync(self) -> Optional[Dict[str, np.ndarray]]:
        if self.packing:
            return self._assemble_packed()
        indices = self.sampler.next_indices(self.batch_size)
        if indices is None:
            return None
        return self._build_examples(indices)

    def _assemble_packed(self) -> Optional[Dict[str, np.ndarray]]:
        """One packed batch: top the pending-example buffer up to
        batch_size * packing_lookahead indices, first-fit their real lengths
        into batch_size rows, and emit the packed arrays. Unplaced examples
        stay pending (bounded: the first batch_size pending always place, so
        the buffer never exceeds the lookahead window) WITH their built rows
        cached — each example is gathered and masked exactly once no matter
        how many batches it waits through. At epoch end a batch is only
        emitted if every row holds at least one example — the packed
        analogue of the unpacked loader's dropped partial tail."""
        from bert_pytorch_tpu.data import packing as packing_lib

        def concat(a, b):
            return ({k: np.concatenate([a[k], b[k]]) for k in a}
                    if a is not None else b)

        if self._pending_built is None and self._pending_examples:
            # restored from a checkpoint: indices only — rebuild once
            self._pending_built = self._build_examples(
                np.asarray(self._pending_examples, np.int64))

        target = self.batch_size * self.packing_lookahead
        exhausted = False
        while len(self._pending_examples) < target:
            idx = self.sampler.next_indices(self.batch_size)
            if idx is None:
                exhausted = True
                break
            self._pending_examples.extend(int(i) for i in idx)
            self._pending_built = concat(self._pending_built,
                                         self._build_examples(idx))
        if not self._pending_examples:
            return None
        examples = self._pending_built
        seq_len = examples["input_ids"].shape[1]
        lengths = packing_lib.example_lengths(examples["attention_mask"])
        bins = packing_lib.first_fit(lengths, self.batch_size, seq_len,
                                     self.packing_max_segments)
        if exhausted and any(not members for members in bins):
            # dropped tail, like the unpacked loader
            self._pending_examples = []
            self._pending_built = None
            return None
        batch = packing_lib.pack_examples(examples, bins, seq_len,
                                          self.packing_max_segments)
        if self.objective == "clm":
            batch = {k: batch[k] for k in CLM_FIELDS}
        placed = {i for members in bins for i in members}
        keep = [pos for pos in range(len(self._pending_examples))
                if pos not in placed]
        self._pending_examples = [self._pending_examples[pos]
                                  for pos in keep]
        self._pending_built = ({k: v[keep] for k, v in examples.items()}
                               if keep else None)
        return batch

    def _build_examples(self, indices: np.ndarray
                        ) -> Dict[str, np.ndarray]:
        raw = self._gather_rows(indices)
        input_ids = raw["input_ids"].astype(np.int32)
        batch: Dict[str, np.ndarray] = {}

        if self.objective == "clm":
            if "special_token_positions" not in raw:
                raise ValueError("objective 'clm' reads the unmasked shard "
                                 "schema (special_token_positions)")
            mask = masking.input_mask_from_specials(
                input_ids, raw["special_token_positions"]).astype(np.int32)
            if not self.packing:    # one document a row
                positions = np.arange(input_ids.shape[1], dtype=np.int32)
                return {"input_ids": input_ids, "attention_mask": mask,
                        "segment_ids": mask, "position_ids": positions * mask}
            # the packer's other per-example fields, empty: the packed batch
            # keeps CLM_FIELDS only
            batch = {"input_ids": input_ids, "attention_mask": mask,
                     "token_type_ids": np.zeros_like(input_ids),
                     "masked_lm_labels": np.full_like(input_ids, -1)}
        elif "special_token_positions" in raw:
            specials = raw["special_token_positions"]
            batch["token_type_ids"] = masking.segment_ids_from_specials(
                input_ids, specials).astype(np.int32)
            batch["attention_mask"] = masking.input_mask_from_specials(
                input_ids, specials).astype(np.int32)
            # per-example cursor-derived rng: resume and the packer's
            # carry-over rebuild re-derive identical masks regardless of
            # how examples were grouped into assembly windows. Only the
            # per-row DRAWS come from per-row generators; the masking
            # logic itself stays one vectorized batch call (a per-row
            # dynamic_mask_batch loop would scale the host assembly cost
            # with batch_size — ruinous at production host batches)
            epoch = self.sampler.epoch
            rngs = [np.random.default_rng(
                        [self._mask_seed, epoch, int(i)])
                    for i in indices]
            masked, labels = masking.dynamic_mask_batch(
                input_ids, specials,
                mask_token_index=self.mask_token_index,
                max_pred_per_seq=self.max_pred_per_seq,
                masked_lm_prob=self.masked_lm_prob,
                vocab_size=self.vocab_size,
                draws=masking.per_row_mask_draws(
                    rngs, input_ids.shape[1], self.vocab_size),
                original_token_prob=self.original_token_prob,
                random_token_prob=self.random_token_prob)
            batch["input_ids"] = masked.astype(np.int32)
            batch["masked_lm_labels"] = labels.astype(np.int32)
        else:  # legacy premasked NVIDIA format
            batch["input_ids"] = input_ids
            batch["token_type_ids"] = raw["segment_ids"].astype(np.int32)
            batch["attention_mask"] = raw["input_mask"].astype(np.int32)
            batch["masked_lm_labels"] = masking.labels_from_premasked(
                input_ids, raw["masked_lm_positions"],
                raw["masked_lm_ids"]).astype(np.int32)

        batch["next_sentence_labels"] = (
            raw["next_sentence_labels"].reshape(-1).astype(np.int32))
        return batch

    def _state_snapshot(self):
        """Live loader state: the sampler cursor plus (under packing) the
        pending-example indices not yet placed in a row. Flat dict, JSON
        serializable — rides in the checkpoint 'extra' payload."""
        state = self.sampler.state_dict()
        if self.packing:
            state["pending"] = list(self._pending_examples)
        return state

    def state_dict(self):
        """Loader state as of the last YIELDED batch — safe to checkpoint
        even with assembly running ahead (prefetch_batches > 0). Without
        prefetch the sampler is never ahead, so its live state is identical
        and callers that mutate the sampler directly stay coherent."""
        if self._assembler is None:
            return self._state_snapshot()
        return dict(self._last_state)

    def load_state_dict(self, state):
        self._drain_queue()
        self.sampler.load_state_dict(state)
        # packed carry-over buffer: restored as global indices (re-gathered
        # on the next assembly); absent in unpacked/legacy checkpoints.
        # Only restored when the SAMPLER accepted its state — if it refused
        # (dataset/world-size changed, warned and reset), the checkpointed
        # indices belong to the old index space and must be dropped with it
        sampler_restored = (
            state.get("total_size") == self.sampler.total_size
            and state.get("world_size") == self.sampler.world_size)
        self._pending_examples = ([int(i) for i in state.get("pending", [])]
                                  if sampler_restored else [])
        self._pending_built = None
        self._last_state = self._state_snapshot()

    def _drain_queue(self):
        """Wait out in-flight assemblies and drop their results (their
        sampler advances are superseded by the restore/reset that follows)."""
        for f in self._queue:
            try:
                f.result()
            except Exception:
                pass
        self._queue.clear()

    def reset_epoch(self):
        """Epoch rollover that is safe under prefetch (the bare
        sampler.reset_epoch remains correct when prefetching is off)."""
        self._drain_queue()
        self.sampler.reset_epoch()
        self._pending_examples = []
        self._pending_built = None
        self._last_state = self._state_snapshot()

    def close(self):
        """Shut both executors down. Idempotent — run_pretraining's
        try/finally, __del__ on an early-aborted iteration (the consuming
        generator dropped mid-epoch), and an explicit user close may all
        fire; only the first does work, and none of them waits on an
        in-flight prefetch future."""
        if self._closed:
            return
        self._closed = True
        # cancel first — waiting out in-flight assemblies whose results are
        # about to be discarded would stall teardown behind a shard load
        if self._assembler is not None:
            self._assembler.shutdown(wait=False, cancel_futures=True)
        self._queue.clear()
        self._pool.shutdown(wait=False, cancel_futures=True)

    def __del__(self):  # pragma: no cover - GC timing dependent
        try:
            self.close()
        except Exception:
            pass


class DevicePrefetcher:
    """Double-buffered host->device staging over a batch iterator.

    Wraps an iterator of per-host numpy batches and keeps `depth` of them
    already PUT to the device (put_fn: numpy batch -> device-resident form,
    typically stack_microbatches + mesh.host_to_device_batch). jax transfers
    are issued asynchronously, so putting batch N+1 before batch N's step is
    dispatched lets the copy ride the wire while the device computes —
    the h2d StepWatch bucket then measures only the (cheap) issue, and the
    device never idles waiting for input at a step boundary. With depth=0
    this degenerates to a synchronous map (the pre-round-11 behavior).

    Iteration yields (numpy_batch, device_batch) pairs so the consumer
    keeps its host-side uses (token counting, recorder) without a D2H trip.

    Checkpoint coherence: pulling ahead advances the upstream loader past
    what the consumer has dispatched, so `state_fn` (e.g.
    loader.state_dict) is snapshotted right after each upstream pull and
    `state_dict()` reports the snapshot of the last pair YIELDED — a resume
    replays nothing and skips nothing, same contract the loader's own
    assembly prefetch keeps.

    Flight-recorder coherence: the loader's batch_tap fires at the
    loader's yield, which under prefetch is one batch AHEAD of dispatch —
    the ring would bind the wrong batch to a step. Callers move the tap
    here (`prefetcher.batch_tap = recorder.capture_batch`); it fires when
    a pair is yielded to the consumer, i.e. in dispatch order.
    """

    def __init__(self, source, put_fn, depth: int = 1, state_fn=None,
                 batch_tap=None):
        self._source = iter(source)
        self._put = put_fn
        self.depth = max(0, int(depth))
        self._state_fn = state_fn
        self.batch_tap = batch_tap
        self._buf: List[tuple] = []  # (np_batch, device_batch, state)
        self._last_state = state_fn() if state_fn is not None else None
        self._exhausted = False

    def _pull(self) -> bool:
        try:
            batch = next(self._source)
        except StopIteration:
            self._exhausted = True
            return False
        state = self._state_fn() if self._state_fn is not None else None
        self._buf.append((batch, self._put(batch), state))
        return True

    def __iter__(self):
        return self

    def __next__(self):
        while not self._exhausted and len(self._buf) < self.depth + 1:
            if not self._pull():
                break
        if not self._buf:
            raise StopIteration
        batch, device_batch, state = self._buf.pop(0)
        self._last_state = state
        if self.batch_tap is not None:
            self.batch_tap(batch)
        return batch, device_batch

    def state_dict(self):
        """Upstream state as of the last yielded pair (None when no
        state_fn was given)."""
        return self._last_state
