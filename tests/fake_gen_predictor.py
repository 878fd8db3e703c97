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
