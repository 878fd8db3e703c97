"""What ``GenScheduler`` calls of a predictor, without a model: every
decode picks token 7, and the page pool never runs out.  Shared by the
scheduler tests that need no device (``test_cost.py``,
``test_obs_fleet.py``); subclass it to change a size."""

import numpy as np


class FakeGenPredictor:
    num_slots, vocab_size, max_len = 4, 8, 32
    max_prompt_len, eos_id = 16, -1
    state_vars = ()
    cache_row_bytes = 4
    last_step_counts = {}
    free_pages = 1 << 20

    def __init__(self):
        self.prefill_calls = []

    def _logits(self, rows):
        out = np.zeros((rows, self.vocab_size), np.float32)
        out[:, 7] = 1.0
        return out

    def prefill(self, prompt):
        self.prefill_calls.append(tuple(prompt))
        return self._logits(1)[0], [np.zeros((1, 1), np.float32)]

    def prefill_cost(self, prompt_len):
        return 100.0 * prompt_len

    def pages_needed(self, prompt_len, max_new_tokens=1):
        return 1

    def alloc_slot_pages(self, slot, n):
        return [slot]

    def free_slot_pages(self, slot):
        return 1

    def free_all_pages(self):
        return 0

    def write_slot(self, slot, kv, prompt_len):
        return 0

    def clear_slot(self, slot):
        pass

    def dispatch_turn(self, tokens, positions, lens):
        return np.argmax(self._logits(self.num_slots), axis=-1)

    def read_turn(self, read):
        return read.tolist(), {}


class FakeChunkPredictor(FakeGenPredictor):
    """The fake with a CHUNK prefill of 4 rows: what ``GenScheduler``
    calls of a bundle whose admissions are runs of chunks.  ``events``
    is the order the device would see: ``("chunk", slot, start, rows)``
    and ``("step", live slots)``.  ``gate`` (a ``threading.Event``, with
    ``gate_at`` a chunk's start) holds the scheduler thread inside that
    chunk until the test sets it; ``fail_at`` makes that chunk raise."""
    max_len, max_prompt_len = 1 << 20, 64
    prefill_chunks = (4,)

    def __init__(self):
        super().__init__()
        self.events, self.held, self.freed, self.cleared = [], set(), [], []
        self.gate = self.gate_at = self.fail_at = self.reached = None

    def chunk_spans(self, prompt_len):
        return [(a, min(a + 4, prompt_len)) for a in range(0, prompt_len, 4)]

    def chunk_cost(self, start, n):
        return 100.0

    def prefill(self, prompt):
        raise AssertionError("the scheduler admits a chunk bundle through "
                             "prefill_chunk, never a whole prompt")

    def prefill_chunk(self, slot, ids, start):
        assert slot in self.held and 0 < len(ids) <= 4
        if start == self.fail_at:
            raise RuntimeError("the chunk's device call failed")
        self.events.append(("chunk", slot, start, len(ids)))
        if self.gate is not None and start == self.gate_at:
            self.reached.set()
            assert self.gate.wait(10)
        return self._logits(1)

    def alloc_slot_pages(self, slot, n):
        assert slot not in self.held
        self.held.add(slot)
        return [slot]

    def free_slot_pages(self, slot):
        self.held.discard(slot)
        self.freed.append(slot)
        return 1

    def free_all_pages(self):
        self.held.clear()
        return 0

    def clear_slot(self, slot):
        self.cleared.append(slot)

    def dispatch_turn(self, tokens, positions, lens):
        live = tuple(int(i) for i in np.flatnonzero(lens))
        assert all(slot in self.held for slot in live)
        self.events.append(("step", live))
        return super().dispatch_turn(tokens, positions, lens)
