"""Multi-file dataset reader: many Lance fragments, one IO path.

The pre-dataset world built one ``TieredStore`` per ``FileReader`` — N files
meant N disjoint NVMe caches and N separate queue drains per logical
operation.  ``DatasetReader`` opens every fragment against **one** shared
:class:`~repro.store.TieredStore` + :class:`~repro.store.IOScheduler` over
the dataset's concatenated global address space (see
:mod:`repro.dataset.manifest`):

* ``take_columns(columns, global_rows)`` vector-maps rows to fragments
  (searchsorted over fragment row starts) once, fans out per-fragment
  batched leaf takes of every column that all enqueue into **one**
  scheduler batch — spans from different files and columns coalesce per
  dependency phase and the whole take is priced as a single queue drain —
  decodes the mini-block chunks of every leaf together (one kernel launch
  per level class), then per column stitches the per-fragment leaves
  together and restores request order with one shared
  :func:`~repro.core.encodings_base.reorder_leaf_rows` permutation;
  ``take(column, global_rows)`` is ``take_columns`` of one column;
* ``scan(column)`` streams every fragment through one prefetch-flagged
  batch, so ``SequentialReadahead`` sees a single global request stream and
  keeps reading ahead **across fragment boundaries** (the inter-file gap is
  just a footer, far below the readahead's ``max_gap``);
* the scheduler's :class:`~repro.store.WorkloadStats` watches the dataset's
  scan/take mix and auto-selects the admission policy of any cache level
  configured ``admission="auto"``.

A multi-column take is one ``dataset.take_columns`` span on the scheduler's
tracer, with ``dataset.locate`` (fragment routing and the per-fragment row
sets), the leaf readers' spans (the grouped ``miniblock.decode_batch``
among them), the batch's ``drain:*`` span and ``dataset.assemble``
(stitching, request order and unshredding) inside it; ``take`` wraps it in
a ``dataset.take:<column>`` span.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core import arrays as A
from ..core.encodings_base import concat_leaves, reorder_leaf_rows
from ..core.file import FileReader, type_from_dict
from ..core.io_sim import DiskView
from ..core.miniblock import decode_plans
from ..core.shred import unshred

from .manifest import Manifest, build_dataset_disk

__all__ = ["DatasetReader"]

SPAN_TAKE_COLUMNS = "dataset.take_columns"
SPAN_LOCATE = "dataset.locate"
SPAN_ASSEMBLE = "dataset.assemble"


class DatasetReader:
    """Reads a fragmented Lance dataset behind one shared store/scheduler.

    ``files`` is the ordered fragment list (raw file bytes).  ``store``
    accepts the same specs as :func:`repro.store.make_store` — the spec is
    resolved once over the dataset's global disk, so "tiered" gives the
    whole dataset a single NVMe budget (and "tiered-auto" additionally lets
    the workload mix pick the admission policy).
    """

    def __init__(self, files: Sequence[bytes], store=None,
                 queue_depth: int = 256, readahead="auto",
                 decode: Optional[str] = None, dict_cached: bool = False,
                 tracer=None):
        from ..store import IOScheduler, make_store

        manifest, disk = build_dataset_disk(files)
        scheduler = IOScheduler(make_store(store, disk),
                                queue_depth=queue_depth, readahead=readahead,
                                tracer=tracer)
        self._bind(manifest, disk, scheduler, decode=decode,
                   dict_cached=dict_cached)

    @classmethod
    def from_manifest(cls, manifest: Manifest, disk, scheduler,
                      decode: Optional[str] = None, dict_cached: bool = False,
                      readers: Optional[List[FileReader]] = None,
                      ) -> "DatasetReader":
        """View an already-materialized dataset (a manifest *version* over a
        shared disk + scheduler) without rebuilding the address space.  The
        dataset writer uses this for time travel: one reader per committed
        version, all sharing the writer's store/cache.  ``readers`` supplies
        pre-built per-fragment ``FileReader``\\ s (cached by the writer so a
        fragment's footer is parsed once, not once per version)."""
        self = cls.__new__(cls)
        self._bind(manifest, disk, scheduler, decode=decode,
                   dict_cached=dict_cached, readers=readers)
        return self

    def _bind(self, manifest, disk, scheduler, decode=None,
              dict_cached=False, readers=None):
        self.manifest = manifest
        self.disk = disk
        self.store = scheduler.store
        self.scheduler = scheduler
        self.tracer = scheduler.tracer
        self.fragments: List[FileReader] = readers if readers is not None else [
            FileReader(DiskView(self.disk, f.base, f.nbytes),
                       scheduler=self.scheduler, base=f.base,
                       decode=decode, dict_cached=dict_cached)
            for f in self.manifest.fragments
        ]
        self.columns = self.fragments[0].columns

    # -- geometry ------------------------------------------------------------
    @property
    def n_rows(self) -> int:
        return self.manifest.n_rows

    @property
    def n_fragments(self) -> int:
        return self.manifest.n_fragments

    def locate(self, rows):
        """Vector-map global row ids to ``(fragment index, local row)``."""
        return self.manifest.locate(rows)

    # -- public API ----------------------------------------------------------
    def take(self, name: str, rows) -> A.Array:
        """Random access by *global* row ids (any order, duplicates fine):
        :meth:`take_columns` of one column, inside a
        ``dataset.take:<column>`` span."""
        rows = np.asarray(rows, dtype=np.int64)
        with self.tracer.span(f"dataset.take:{name}", cat="reader",
                              n_rows=len(rows)):
            return self.take_columns([name], rows)[name]

    def take_columns(self, names: Sequence[str], rows) -> Dict[str, A.Array]:
        """Whole rows by *global* row ids: ``{name: column}`` for every
        column named, each in request order with duplicates, as
        :meth:`take` returns it.

        One ``locate`` routes the rows; one scheduler batch covers every
        column's reads in every fragment, so per-phase coalescing and
        queue-depth pricing see the union of all files' spans and the
        request is one queue drain; the mini-block leaves of every column
        and fragment are decoded together (:func:`decode_plans`: one kernel
        launch per level class under ``decode="pallas"``); then each
        column is assembled once.  The result is bit-identical to running
        each fragment's take separately and reassembling.
        """
        rows = np.asarray(rows, dtype=np.int64)
        names = list(dict.fromkeys(names))
        cols = {n: self.columns[n] for n in names}
        tracer = self.tracer
        with tracer.span(SPAN_TAKE_COLUMNS, cat="reader",
                         n_columns=len(names), n_rows=len(rows)) as span:
            with tracer.span(SPAN_LOCATE):
                fi, local = self.locate(rows)
                # concat order = request rows stably grouped by fragment;
                # inv maps each request position to its row in that
                # concatenation
                perm = np.argsort(fi, kind="stable")
                inv = np.empty(len(perm), dtype=np.int64)
                inv[perm] = np.arange(len(perm), dtype=np.int64)
                # an empty request still reads fragment 0, for empty columns
                frag_ids = np.unique(fi) if len(rows) else np.zeros(1, int)
                local_rows = [local[fi == f] for f in frag_ids]
            span.set(n_fragments=len(frag_ids))
            label = (f"take:{names[0]}" if len(names) == 1
                     else f"take:{len(names)}-columns")
            with self.scheduler.batch(label) as io:
                # the global rows are the logical requests this drain's
                # modeled cost is attributed over (repro.obs.attrib)
                io.note_requests(len(rows))
                plans: list = []  # every mini-block leaf's, decoded at once
                frags = [self.fragments[f] for f in frag_ids]
                parts = {n: [fr.take_leaves(n, lr, io, plans)
                             for fr, lr in zip(frags, local_rows)]
                         for n in names}
                decode_plans(plans, tracer)
                parts = {n: [fr.finish_leaves(p, io)
                             for fr, p in zip(frags, parts[n])]
                         for n in names}
            with tracer.span(SPAN_ASSEMBLE):
                return {n: _assemble(cols[n], parts[n], inv) for n in names}

    def scan(self, name: str, io_chunk: int = 8 << 20) -> A.Array:
        """Full-column scan across all fragments, in global row order."""
        with self.tracer.span(f"dataset.scan:{name}", cat="reader",
                              n_fragments=len(self.fragments)):
            with self.scheduler.batch(f"scan:{name}", prefetch=True) as io:
                parts = [fr.scan_into(name, io, io_chunk=io_chunk)
                         for fr in self.fragments]
            return A.concat(parts)

    # -- accounting ----------------------------------------------------------
    def io_stats(self, coalesce_gap: int = 0):
        """Logical-trace stats over the shared scheduler (all fragments)."""
        return self.scheduler.stats(coalesce_gap)

    def tier_stats(self):
        """Per-tier dispatched-IO stats of the shared store."""
        return self.store.tier_stats()

    def workload_stats(self):
        """The shared scheduler's scan/take mix observer."""
        return self.scheduler.workload

    def modelled_time(self, queue_depth: Optional[int] = None) -> float:
        return self.scheduler.model_time(queue_depth)

    def search_cache_bytes(self, name: Optional[str] = None) -> int:
        return sum(fr.search_cache_bytes(name) for fr in self.fragments)

    def data_bytes(self, name: Optional[str] = None) -> int:
        return sum(fr.data_bytes(name) for fr in self.fragments)

    def reset_io(self) -> None:
        """Zero trace/tier counters; cache residency survives (warm stays
        warm — :meth:`drop_caches` is the cold restart)."""
        self.scheduler.reset()

    def drop_caches(self) -> None:
        self.store.drop_caches()


def _assemble(col: dict, parts: list, inv: np.ndarray) -> A.Array:
    """One column of a take out of its per-fragment parts: stitched,
    in request order, unshredded."""
    if col["kind"] in ("arrow", "packed"):
        return A.concat(parts).take(inv)
    leaves = [reorder_leaf_rows(concat_leaves([p[k] for p in parts]), inv)
              for k in range(len(parts[0]))]
    return unshred(leaves, type_from_dict(col["type"]))
