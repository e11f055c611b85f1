"""Tokenizer loading with an offline-safe fallback.

The reference hard-depends on a Hugging Face hub tokenizer
(``unsloth/Mistral-Nemo-Base-2407-bnb-4bit``, ref: utils.py:133-137,
train.py:28) — which requires network or a warm cache. TPU pods frequently run
with no egress, so this framework adds a first-party ``ByteTokenizer``
(UTF-8 bytes + BOS/EOS/PAD specials) selectable as
``--tokenizer-name-or-path byte`` and used as an automatic fallback when the
HF tokenizer cannot be loaded offline.

Only the tokenizer surface the reference actually uses is required:
``encode_plus(text, max_length=, padding=, truncation=, padding_side=)``
returning ``{"input_ids": [...]}`` (ref: dataset.py:29-35,84-89), plus
``vocab_size`` / ``pad_token_id`` / ``bos_token_id`` / ``decode``
(ref: train.py:30,51; dataset.py:58,122).
"""

import logging
from typing import Dict

import numpy as np

from .native import byte_tokenize

logger = logging.getLogger()


class ByteTokenizer:
    """UTF-8 byte-level tokenizer: ids 0..2 = PAD/BOS/EOS, 3..258 = bytes."""

    pad_token_id = 0
    bos_token_id = 1
    eos_token_id = 2
    _OFFSET = 3

    @property
    def vocab_size(self) -> int:
        return 256 + self._OFFSET

    def encode(self, text: str, add_bos: bool = True) -> np.ndarray:
        return byte_tokenize(text, self.bos_token_id if add_bos else -1,
                             self._OFFSET)

    def encode_plus(self, text: str, max_length: int = None, padding=False,
                    truncation: bool = False, padding_side: str = "right"
                    ) -> Dict[str, np.ndarray]:
        ids = self.encode(text)
        if truncation and max_length is not None:
            ids = ids[:max_length]
        if (padding == "max_length" and max_length is not None
                and len(ids) < max_length):
            pad = np.full((max_length - len(ids),), self.pad_token_id,
                          np.int32)
            ids = (np.concatenate([ids, pad]) if padding_side == "right"
                   else np.concatenate([pad, ids]))
        return {"input_ids": ids}

    def decode(self, ids) -> str:
        # ids past the byte range exist whenever the model's vocab is
        # padded wider than the tokenizer's (--vocab-size 50257 over
        # bytes); they carry no text, like the specials
        data = bytes(int(i) - self._OFFSET for i in ids
                     if self._OFFSET <= int(i) < self.vocab_size)
        return data.decode("utf-8", errors="replace")


def load_tokenizer(name_or_path: str):
    """HF tokenizer by name/path, or ByteTokenizer for 'byte' / offline."""
    if name_or_path in ("byte", "byte://", ""):
        return ByteTokenizer()
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(name_or_path)
    except Exception as e:  # offline, missing cache, bad name, ...
        logger.warning(
            "Could not load HF tokenizer %r (%s); falling back to the "
            "built-in byte tokenizer", name_or_path, type(e).__name__)
        return ByteTokenizer()
