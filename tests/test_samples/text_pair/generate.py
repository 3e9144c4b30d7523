"""Deterministic generator for the committed text-pair paraphrase fixture.

Zero-egress stand-in for the reference's GLUE/MRPC gate data
(reference test_utils/training.py:64 downloads MRPC; tests/test_samples/MRPC
holds its local CSVs). Here the task is synthetic paraphrase detection over a
closed vocabulary with a known generative process, so a from-scratch bert-tiny
can provably learn it — and a *mis-trained* one provably cannot (the mutation
audit in tests/test_integration_gates.py).

Task design (all constraints found empirically on the CPU test tier):
- A sentence is 5 active-voice slots: `adj noun verb adj noun`
  ("big dog chases small cat").
- Every word has exactly one synonym partner. A POSITIVE pair rewrites each
  slot to its partner with p=0.5 (so positives are NOT string-equal).
- A NEGATIVE pair replaces m ~ Uniform{1..4} slots with a same-class word that
  is neither the original nor its partner (single-slot negatives are the hard
  decision boundary; 4-slot ones keep early training off the saddle).
- 56 words (8 adj / 12 noun / 8 verb synonym pairs) and 6144 train examples:
  the synonym-matching circuit only emerges when each pair is seen often
  enough. Calibrated on this machine: 112 words x 2048 examples memorizes
  without generalizing (dev 0.61); 56 x 6144 crosses dev 0.87 at epoch 8 and
  0.93 at 11 (adamw 3e-4, wd 0.01, global batch 32, from-scratch bert-tiny).
- dev 128, balanced, sentence pairs disjoint between splits.

Run `python generate.py` from this directory to regenerate train.csv, dev.csv,
vocab.txt byte-identically (committed output; tests never run this).
"""

import csv
import pathlib

import numpy as np

ADJ_PAIRS = [
    ("big", "large"), ("small", "tiny"), ("quick", "fast"), ("slow", "sluggish"),
    ("happy", "glad"), ("sad", "unhappy"), ("bright", "shiny"), ("dark", "dim"),
]
NOUN_PAIRS = [
    ("dog", "hound"), ("cat", "feline"), ("child", "kid"), ("doctor", "physician"),
    ("lawyer", "attorney"), ("teacher", "instructor"), ("house", "home"),
    ("car", "automobile"), ("boat", "ship"), ("road", "street"), ("stone", "rock"),
    ("hill", "mound"),
]
VERB_PAIRS = [
    ("chases", "pursues"), ("sees", "spots"), ("likes", "enjoys"),
    ("hates", "detests"), ("builds", "constructs"), ("breaks", "shatters"),
    ("buys", "purchases"), ("sells", "vends"),
]

SLOT_PAIRS = [ADJ_PAIRS, NOUN_PAIRS, VERB_PAIRS, ADJ_PAIRS, NOUN_PAIRS]


def partner(word):
    for pairs in (ADJ_PAIRS, NOUN_PAIRS, VERB_PAIRS):
        for a, b in pairs:
            if word == a:
                return b
            if word == b:
                return a
    raise KeyError(word)


def sample_sentence(rng):
    return [pairs[rng.integers(len(pairs))][rng.integers(2)] for pairs in SLOT_PAIRS]


def make_pair(rng, label):
    a = sample_sentence(rng)
    if label == 1:
        b = [partner(w) if rng.integers(2) else w for w in a]
    else:
        b = list(a)
        m = int(rng.integers(1, 5))
        slots = rng.choice(5, size=m, replace=False)
        for s in slots:
            pairs = SLOT_PAIRS[s]
            banned = {a[s], partner(a[s])}
            while True:
                pick = pairs[rng.integers(len(pairs))][rng.integers(2)]
                if pick not in banned:
                    break
            b[s] = pick
        # the untouched slots still paraphrase freely
        b = [partner(w) if (i not in slots and rng.integers(2)) else w for i, w in enumerate(b)]
    return " ".join(a), " ".join(b), label


def write_split(path, rng, n, seen):
    rows = []
    per_label = n // 2
    for label in (1, 0):
        count = 0
        while count < per_label:
            s1, s2, y = make_pair(rng, label)
            if (s1, s2) in seen:
                continue
            seen.add((s1, s2))
            rows.append((s1, s2, y))
            count += 1
    order = rng.permutation(len(rows))
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["sentence1", "sentence2", "label"])
        for i in order:
            w.writerow(rows[i])


def main():
    here = pathlib.Path(__file__).parent
    rng = np.random.default_rng(20260731)
    seen = set()
    write_split(here / "train.csv", rng, 6144, seen)
    write_split(here / "dev.csv", rng, 128, seen)
    words = sorted({w for pairs in (ADJ_PAIRS, NOUN_PAIRS, VERB_PAIRS) for p in pairs for w in p})
    with open(here / "vocab.txt", "w") as f:
        for tok in ["[PAD]", "[CLS]", "[SEP]", "[UNK]", *words]:
            f.write(tok + "\n")
    print(f"wrote {len(words)} words, train 6144, dev 128")


if __name__ == "__main__":
    main()
