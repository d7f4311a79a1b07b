"""The benchmark's word-level tokenizer: token <id> is the word `w<id>`.

Written as HF tokenizer files (the program's `--tokenizer DIR` loads them
with AutoTokenizer, as it loads a real model's) into a fixed directory of
the checkout, from nothing but the vocabulary size: no file is fetched and
none is committed. Ids 0, 1, 2 are <pad>, <s>, </s>; no post-processor, so
encoding adds no special token and a prompt of n words is n tokens."""

from __future__ import annotations

import json
import os

from harness.traffic_lib import N_SPECIAL

SPECIALS = ("<pad>", "<s>", "</s>")
assert len(SPECIALS) == N_SPECIAL


def ensure(cache_root: str, vocab_size: int) -> str:
    """Directory holding the tokenizer for `vocab_size`, made if missing."""
    d = os.path.join(cache_root, "tokenizers", f"words-{int(vocab_size)}")
    done = os.path.join(d, "tokenizer_config.json")
    if os.path.isfile(done):
        return d
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {s: i for i, s in enumerate(SPECIALS)}
    vocab.update({f"w{i}": i for i in range(N_SPECIAL, int(vocab_size))})
    tok = Tokenizer(models.WordLevel(vocab, unk_token=SPECIALS[0]))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    os.makedirs(d, exist_ok=True)
    tok.save(os.path.join(d, "tokenizer.json"))
    tmp = done + ".tmp"
    with open(tmp, "w") as f:
        json.dump({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "pad_token": SPECIALS[0], "unk_token": SPECIALS[0],
            "bos_token": SPECIALS[1], "eos_token": SPECIALS[2],
        }, f)
    os.replace(tmp, done)  # the config lands last: its presence means "complete"
    return d
