"""Paged KV-cache memory subsystem (vLLM-style block space management).

Why
---
The dense engine preallocates one ``max_len``-long cache row per slot, so
HBM capacity is consumed by the *worst-case* sequence length of every
admitted request.  Real workloads are short on average and long in the
tail, so most of that reservation is internal fragmentation — which caps
the decode batch size and therefore how many decodes can piggyback on a
SARATHI chunk.  The paged layout (Sarathi-Serve / vLLM) instead carves the
KV pool into fixed-size **blocks** and maps each request's logical token
positions onto physical blocks through a per-request **block table**, so a
request only ever holds ``ceil(context / block_size)`` blocks.

Memory model
------------
* The pool is ``[n_blocks, n_kv_heads, 2, block_size, head_dim]`` per
  layer (K and V fused on the pair axis); every layer shares ONE block
  table per request (vLLM's layout), so the :class:`BlockManager` does
  its bookkeeping once for the whole model.
* Physical block **0 is reserved as the scratch block**: padded batch
  entries (the no-chunk iteration, unused decode lanes) point their whole
  block table at it, so their writes land somewhere harmless — this
  subsumes the dense engine's extra ``n_slots + 1`` scratch *row* (a full
  ``max_len`` of HBM) with a single block.
* Allocation is a free-list pop; nothing is zeroed on free.  Freed blocks
  self-heal exactly like dense rows: garbage KV is either overwritten
  before it becomes visible or hidden by the causal / context-length mask.

Tuning
------
* ``block_size`` trades internal fragmentation (up to ``block_size - 1``
  wasted token slots per request) against table length and per-block
  bookkeeping; 16–32 suits CPU/interpret runs, 128 aligns the Pallas
  kernels' KV tiles with the MXU lane width on real TPUs.
* ``n_blocks`` sets the HBM budget: ``n_blocks * block_size`` pooled token
  slots replace the dense ``(n_slots + 1) * max_len`` reservation.  At
  equal HBM the pool admits ~``max_len / avg_len`` times more concurrent
  requests.
* ``watermark`` (fraction of usable blocks) gates *admission* only: a new
  request is admitted when its whole prompt fits with the watermark to
  spare, which keeps headroom for the running requests' decode appends and
  makes immediate re-preemption unlikely.

Preemption semantics
--------------------
When a decode append finds the pool dry, the scheduler preempts the
lowest-priority (latest-admitted) running request.  What happens to the
victim's KV is the scheduler's ``preempt_mode``:

* **recompute** (default) — its blocks are freed and its request state
  is reset (prompt + generated tokens re-enter as one prefill); cost is
  tracked per request as ``recompute_tokens``;
* **swap** — with ``host_blocks > 0`` the :class:`BlockManager` also
  owns a host-RAM tier of block-sized slots (the engine mirrors it with
  a pinned numpy arena): ``swap_out`` moves the victim's whole mapping
  to host slots and returns its device blocks to the free list,
  ``swap_in`` rebuilds the table from fresh blocks and streams the
  bytes back before the victim's next chunk.  Only fully *exclusive*
  tables are swappable — a block shared with another request or pinned
  by the prefix cache outlives the victim, so those victims fall back
  to recompute.  The host ledger keeps its own conservation invariant,
  ``n_host_free + n_swapped == n_host_slots``, mirroring the device
  pool's ``n_free + n_referenced == n_usable``;
* **hybrid** — per victim, the cost model compares the PCIe round trip
  (``2 * kv_swap_time``) against re-prefilling the context and picks
  the cheaper restore path.

In every mode the victim rejoins the head of the waiting queue.  Under
greedy sampling all three are exact — swap restores the very bytes
recompute would regenerate — so preemption is invisible in the output
stream and shows up only as latency and swap/recompute traffic.
"""
from repro.cache.block_manager import BlockManager, PoolExhausted
from repro.cache.prefix_cache import PrefixCache

__all__ = ["BlockManager", "PoolExhausted", "PrefixCache"]
