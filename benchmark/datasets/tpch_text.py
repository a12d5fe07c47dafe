"""The data set ``tpch_text``: ``tpch``'s eight tables with the first
free-text column, ``orders.o_comment varchar(79)`` (TPC-H v3 cl. 1.4.1,
text of cl. 4.2.2.10).  The other 49 columns are ``tpch``'s own, value
for value, for the same ``(seed, sf)``: this module calls
``tpch.generate`` for them, so every template that runs on ``tpch`` has
the same reference answer here.

``o_comment`` is made as dbgen makes it: a pool of text from the
specification's grammar (sentences of noun, verb and prepositional
phrases over its weighted word lists), and for every order a substring
of it at a random offset with a random length of 19..78 characters, so
a comment starts and ends mid-word as dbgen's do.  The pool comes from
one random stream (``default_rng([seed, 9])``), offsets and lengths from
one stream a page of orders (``default_rng([seed, 8, page])``): neither
is ``tpch``'s, so the column depends on the seed and the scale factor
alone -- never on which columns are asked for.  The column is
represented like every string column, ``(int32 codes, [bytes, ...])``
with the dictionary sorted and unique; it is practically as long as the
table (1,490,217 entries for 1.5M rows at SF1 on seed 3700000101: a
short comment cut from a common phrase recurs).

The word lists and their weights are from memory of dbgen's
``dists.dss``; the configuration lists them under ``assumed``.  It is
NOT dbgen: the share of comments ``'%special%requests%'`` matches is
what this grammar gives (``MATCHED_SHARE``), not dbgen's.
"""

import numpy as np

from benchmark.datasets import tpch
from benchmark.datasets.tpch import PAGE_ORDERS, days, values  # noqa: F401

#: bump when any column's values change for a given (seed, sf)
GEN_VERSION = 1

DB = "tpch_text"
SQL_TABLES = tpch.SQL_TABLES

SCHEMA = {t: dict(cols) for t, cols in tpch.SCHEMA.items()}
SCHEMA["orders"]["o_comment"] = "varchar(79)"

#: (min, max) characters of an O_COMMENT (cl. 4.2.3: text of average
#: length 49, from 0.4 to 1.6 times it)
COMMENT_CHARS = (19, 78)
#: share of orders whose comment matches Q13's '%special%requests%', as
#: this grammar gives it (seeds 1..8 at SF 0.1: 1.18% .. 1.28%; 1.22% at
#: SF1 on seed 3700000101; dbgen's is about 1%); tests/test_tpch_text.py holds two seeds to the tolerance
MATCHED_SHARE = 0.012
MATCHED_SHARE_TOLERANCE = 0.002

#: streams of numpy.random.default_rng that are not a tpch table's
_ROW_STREAM = len(tpch.SCHEMA)          # [seed, 8, page]
_POOL_STREAM = len(tpch.SCHEMA) + 1     # [seed, 9]
#: pool characters per order (a power of two over it)
_POOL_CHARS_PER_ORDER = 16


def _weighted(text: str) -> list:
    """'word|weight word|weight ...' -> the words, each `weight` times
    (a uniform draw over the list is the weighted draw); '_' is a space
    inside a word."""
    out = []
    for item in text.split():
        word, _, weight = item.partition("|")
        out += [word.replace("_", " ").encode()] * int(weight or 1)
    return out


NOUNS = _weighted(
    "packages|40 requests|40 accounts|40 deposits|40 foxes|20 ideas|20 "
    "theodolites|20 pinto_beans|20 instructions|20 dependencies|10 "
    "excuses|10 platelets|10 asymptotes|10 courts|5 dolphins|5 "
    "multipliers sauternes warthogs frets dinos attainments somas "
    "Tiresias' patterns forges braids hockey_players frays warhorses "
    "dugouts notornis epitaphs pearls tithes waters orbits gifts sheaves "
    "depths sentiments decoys realms pains grouches escapades")
VERBS = _weighted(
    "sleep|20 wake|20 are|20 cajole|20 haggle|20 nag|10 use|10 boost|10 "
    "affix|5 detect|5 integrate|5 maintain nod was lose sublate solve "
    "thrash promise engage hinder print x-ray breach eat grow impress "
    "mold poach serve run dazzle snooze doze unwind kindle play hang "
    "believe doubt")
ADJECTIVES = _weighted(
    "special|20 pending|20 unusual|20 express|20 furious sly careful "
    "blithe quick fluffy slow quiet ruthless thin close dogged daring "
    "brave stealthy permanent enticing idle busy regular|20 final|40 "
    "ironic|40 even|30 bold|20 silent|10")
ADVERBS = _weighted(
    "sometimes always never furiously|50 slyly|50 carefully|50 "
    "blithely|40 quickly|30 fluffily|20 slowly quietly ruthlessly thinly "
    "closely doggedly daringly bravely stealthily permanently enticingly "
    "idly busily regularly finally ironically evenly boldly silently")
PREPOSITIONS = _weighted(
    "about|50 above|50 according_to|50 across|50 after|50 against|40 "
    "along|40 alongside_of|30 among|30 around|20 at|10 atop before "
    "behind beneath beside besides between beyond by despite during "
    "except for from in_place_of inside instead_of into near of on "
    "outside over past since through throughout to toward under until "
    "up upon without with within")
AUXILIARIES = _weighted(
    "do may might shall will would can could should ought_to must "
    "will_have_to shall_have_to could_have_to should_have_to "
    "must_have_to need_to try_to")
TERMINATORS = _weighted(".|50 ; : ? ! --")

# -- the grammar, expanded to sequences of word classes -----------------------
# classes: N noun, V verb, J adjective, j adjective + comma, D adverb,
# P preposition, X auxiliary, t the word "the", T terminator
_NOUN_PHRASES = ("N", "JN", "jJN", "DJN")
_VERB_PHRASES = ("V", "XV", "VD", "XVD")


def _sentences() -> list:
    """Every expansion of cl. 4.2.2.10's five sentence forms, as strings
    of class letters; a uniform draw over a form's expansions is a
    uniform draw of each of its phrases."""
    np_, vp = _NOUN_PHRASES, _VERB_PHRASES
    pp = ["Pt" + n for n in np_]
    return [
        [a + b + "T" for a in np_ for b in vp],
        [a + b + c + "T" for a in np_ for b in vp for c in pp],
        [a + b + c + "T" for a in np_ for b in vp for c in np_],
        [a + b + c + d + "T" for a in np_ for b in pp for c in vp
         for d in np_],
        [a + b + c + d + "T" for a in np_ for b in pp for c in vp
         for d in pp],
    ]


def _word_table():
    """-> (chars uint8, start int32[W], length int32[W], class_start,
    class_size) over every class's weighted list; a word's bytes carry
    the blank that follows it (`_pool` takes it back before a
    terminator)."""
    classes = {
        "N": [w + b" " for w in NOUNS], "V": [w + b" " for w in VERBS],
        "J": [w + b" " for w in ADJECTIVES],
        "j": [w + b", " for w in ADJECTIVES],
        "D": [w + b" " for w in ADVERBS],
        "P": [w + b" " for w in PREPOSITIONS],
        "X": [w + b" " for w in AUXILIARIES], "t": [b"the "],
        "T": [w + b" " for w in TERMINATORS],
        "_": [b""],                       # padding of a short sentence
    }
    words, class_start, class_size = [], {}, {}
    for letter, lst in classes.items():
        class_start[letter], class_size[letter] = len(words), len(lst)
        words += lst
    length = np.array([len(w) for w in words], dtype=np.int32)
    start = np.cumsum(length, dtype=np.int32) - length
    chars = np.frombuffer(b"".join(words), dtype=np.uint8)
    return chars, start, length, class_start, class_size


#: sentences drawn at a time (about 4M characters: bounded temporaries)
_POOL_BLOCK = 1 << 16


def _pool(seed: int, n_chars: int) -> np.ndarray:
    """`n_chars` characters of grammar text (uint8), from the seed."""
    r = np.random.default_rng([int(seed), _POOL_STREAM])
    chars, start, length, class_start, class_size = _word_table()
    forms = _sentences()
    width = max(len(s) for f in forms for s in f)
    flat = [s.ljust(width, "_") for f in forms for s in f]
    first = np.cumsum([0] + [len(f) for f in forms])
    cls_start = np.array([[class_start[c] for c in s] for s in flat],
                         dtype=np.int32)
    cls_size = np.array([[class_size[c] for c in s] for s in flat],
                        dtype=np.float32)
    term_lo = class_start["T"]
    term_hi = term_lo + class_size["T"]
    blocks, have = [], 0
    while have < n_chars:
        m = _POOL_BLOCK
        form = r.integers(0, len(forms), m)
        variant = first[form] + (r.random(m) * (first[form + 1]
                                                - first[form])).astype(int)
        word = (cls_start[variant]
                + (r.random((m, width), dtype=np.float32)
                   * cls_size[variant]).astype(np.int32)).ravel()
        # "noun terminator": the terminator follows without the space
        # the word before it carries
        wlen = length[word]
        wlen[:-1] -= (word[1:] >= term_lo) & (word[1:] < term_hi)
        # flat concatenation: character k of word i is chars[start[i] + k]
        ends = np.cumsum(wlen)
        src = np.repeat(start[word] - (ends - wlen), wlen) \
            + np.arange(int(ends[-1]), dtype=np.int32)
        blocks.append(chars[src])
        have += len(src)
    return np.concatenate(blocks)[:n_chars]


def pool_chars(sf: float) -> int:
    n = tpch.sizes(sf)["orders"] * _POOL_CHARS_PER_ORDER
    return max(1 << 16, 1 << (n - 1).bit_length())


def _o_comment(seed: int, sf: float) -> tuple:
    """-> (int32 codes, sorted unique [bytes, ...]) of every order."""
    n = tpch.sizes(sf)["orders"]
    lo_chars, hi_chars = COMMENT_CHARS
    pool = _pool(seed, pool_chars(sf))
    cols = np.arange(hi_chars, dtype=np.int32)
    parts = []
    for page, _lo, m in tpch._pages(n, PAGE_ORDERS):
        r = np.random.default_rng([int(seed), _ROW_STREAM, int(page)])
        length = r.integers(lo_chars, hi_chars + 1, m)
        offset = r.integers(0, len(pool) - hi_chars, m).astype(np.int32)
        text = pool[offset[:, None] + cols[None, :]]
        text[cols[None, :] >= length[:, None]] = 0
        parts.append(np.ascontiguousarray(text).view(f"S{hi_chars}")
                     .ravel())
    uniq, codes = np.unique(np.concatenate(parts), return_inverse=True)
    return codes.astype(np.int32), uniq.tolist()


def generate(seed: int, sf: float, want: "dict | None" = None) -> dict:
    """``tpch.generate``'s result for the 49 shared columns, and
    ``orders.o_comment`` where `want` asks for it (default: every
    column)."""
    want = want or {t: list(cols) for t, cols in SCHEMA.items()}
    shared = {t: [c for c in cols if (t, c) != ("orders", "o_comment")]
              for t, cols in want.items()}
    with_comment = "o_comment" in want.get("orders", ())
    if with_comment and not shared["orders"]:
        del shared["orders"]         # o_comment alone: no page of orders
    out = tpch.generate(seed, sf, shared) if shared else {}
    if with_comment:
        orders = out.get("orders", {})
        comment = _o_comment(seed, sf)
        out["orders"] = {c: (comment if c == "o_comment" else orders[c])
                         for c in want["orders"]}
    return {t: out[t] for t in want}


def load(tk, tables: dict, want: dict, seeded: bool, tag: str) -> dict:
    """Install `tables` (generate's result for `want`) through `tk` (worker
    side); -> row counts."""
    from benchmark.harness import install
    return install.load(tk, DB, SCHEMA, SQL_TABLES, tables, want, seeded,
                        tag)


def column_bytes(reads: dict, rows: dict) -> int:
    """``tpch.column_bytes`` over this schema: o_comment counts as its
    4-byte dictionary codes, like every string column (the program reads
    the codes, and the pattern's table by code)."""
    shared = {t: [c for c in cols if c in tpch.SCHEMA[t]]
              for t, cols in reads.items()}
    with_comment = "o_comment" in reads.get("orders", ())
    return tpch.column_bytes(shared, rows) \
        + (4 * rows["orders"] if with_comment else 0)
