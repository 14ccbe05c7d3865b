"""Text analysis: tokenizers, token filters, analyzers.

Reference analog: index/analysis/ (149 files — AnalysisService.java,
AnalysisModule.java, StandardAnalyzerProvider.java, ...). Analysis is a
pure host-side concern in the TPU build — it produces term streams at
index time and query time; only term ids ever reach the device.

Scope: the core analyzers the reference registers by default
(standard/simple/whitespace/keyword/stop/english + custom chains from
settings). The reference's ~30 language analyzers are a registry matter,
not an architecture one; they slot into TOKEN_FILTERS/ANALYZERS as added.
"""

from __future__ import annotations

import re
import unicodedata
from typing import Callable, Iterable

from ..utils.settings import Settings
from ..utils.errors import IllegalArgumentError

# ---------------------------------------------------------------------------
# Tokenizers: text -> list of (term, position)
# ---------------------------------------------------------------------------

_WORD_RE = re.compile(r"[\w][\w'']*", re.UNICODE)
_LETTER_RE = re.compile(r"[^\W\d_]+", re.UNICODE)


def standard_tokenizer(text: str) -> list[str]:
    """Unicode word-boundary tokenizer (approximates Lucene StandardTokenizer,
    ref: index/analysis/StandardTokenizerFactory.java)."""
    return _WORD_RE.findall(text)


def whitespace_tokenizer(text: str) -> list[str]:
    return text.split()


def letter_tokenizer(text: str) -> list[str]:
    return _LETTER_RE.findall(text)


def keyword_tokenizer(text: str) -> list[str]:
    return [text] if text else []


def ngram_tokenizer(min_gram: int = 1, max_gram: int = 2) -> Callable[[str], list[str]]:
    def tokenize(text: str) -> list[str]:
        out = []
        n = len(text)
        for i in range(n):
            for g in range(min_gram, max_gram + 1):
                if i + g <= n:
                    out.append(text[i:i + g])
        return out
    return tokenize


def pattern_tokenizer(pattern: str = r"\W+") -> Callable[[str], list[str]]:
    rx = re.compile(pattern, re.UNICODE)
    return lambda text: [t for t in rx.split(text) if t]


TOKENIZERS: dict[str, Callable] = {
    "standard": standard_tokenizer,
    "whitespace": whitespace_tokenizer,
    "letter": letter_tokenizer,
    "keyword": keyword_tokenizer,
}

# ---------------------------------------------------------------------------
# Token filters: list[str] -> list[str]
# ---------------------------------------------------------------------------

# Lucene's default English stopword set (StopAnalyzer.ENGLISH_STOP_WORDS_SET)
ENGLISH_STOP_WORDS = frozenset(
    "a an and are as at be but by for if in into is it no not of on or such "
    "that the their then there these they this to was will with".split()
)


def lowercase_filter(tokens: list[str]) -> list[str]:
    return [t.lower() for t in tokens]


def uppercase_filter(tokens: list[str]) -> list[str]:
    return [t.upper() for t in tokens]


def stop_filter(stopwords: Iterable[str] = ENGLISH_STOP_WORDS) -> Callable:
    sw = frozenset(stopwords)
    return lambda tokens: [t for t in tokens if t not in sw]


def asciifolding_filter(tokens: list[str]) -> list[str]:
    """Strip diacritics (ref: ASCIIFoldingTokenFilterFactory.java)."""
    return [
        unicodedata.normalize("NFKD", t).encode("ascii", "ignore").decode("ascii") or t
        for t in tokens
    ]


def unique_filter(tokens: list[str]) -> list[str]:
    seen: set[str] = set()
    out = []
    for t in tokens:
        if t not in seen:
            seen.add(t)
            out.append(t)
    return out


def length_filter(min_len: int = 0, max_len: int = 1 << 30) -> Callable:
    return lambda tokens: [t for t in tokens if min_len <= len(t) <= max_len]


def edge_ngram_filter(min_gram: int = 1, max_gram: int = 8) -> Callable:
    def f(tokens: list[str]) -> list[str]:
        out = []
        for t in tokens:
            for g in range(min_gram, min(max_gram, len(t)) + 1):
                out.append(t[:g])
        return out
    return f


# --- Porter stemmer (classic algorithm; ref: PorterStemTokenFilterFactory) --

_VOWELS = "aeiou"


def _is_cons(word: str, i: int) -> bool:
    c = word[i]
    if c in _VOWELS:
        return False
    if c == "y":
        return i == 0 or not _is_cons(word, i - 1)
    return True


def _measure(stem: str) -> int:
    """Number of VC sequences."""
    m = 0
    prev_vowel = False
    for i in range(len(stem)):
        if _is_cons(stem, i):
            if prev_vowel:
                m += 1
            prev_vowel = False
        else:
            prev_vowel = True
    return m


def _has_vowel(stem: str) -> bool:
    return any(not _is_cons(stem, i) for i in range(len(stem)))


def _ends_double_cons(word: str) -> bool:
    return (len(word) >= 2 and word[-1] == word[-2] and _is_cons(word, len(word) - 1))


def _cvc(word: str) -> bool:
    if len(word) < 3:
        return False
    return (_is_cons(word, len(word) - 3) and not _is_cons(word, len(word) - 2)
            and _is_cons(word, len(word) - 1) and word[-1] not in "wxy")


_STEP2 = (("ational", "ate"), ("tional", "tion"), ("enci", "ence"), ("anci", "ance"),
          ("izer", "ize"), ("abli", "able"), ("alli", "al"), ("entli", "ent"),
          ("eli", "e"), ("ousli", "ous"), ("ization", "ize"), ("ation", "ate"),
          ("ator", "ate"), ("alism", "al"), ("iveness", "ive"), ("fulness", "ful"),
          ("ousness", "ous"), ("aliti", "al"), ("iviti", "ive"), ("biliti", "ble"))
_STEP3 = (("icate", "ic"), ("ative", ""), ("alize", "al"), ("iciti", "ic"),
          ("ical", "ic"), ("ful", ""), ("ness", ""))
_STEP4 = tuple(sorted(
    ("al", "ance", "ence", "er", "ic", "able", "ible", "ant", "ement", "ment",
     "ent", "ou", "ism", "ate", "iti", "ous", "ive", "ize"),
    key=len, reverse=True))


def porter_stem(word: str) -> str:
    if len(word) <= 2:
        return word
    w = word

    # step 1a
    if w.endswith("sses"):
        w = w[:-2]
    elif w.endswith("ies"):
        w = w[:-2]
    elif not w.endswith("ss") and w.endswith("s"):
        w = w[:-1]

    # step 1b
    flag = False
    if w.endswith("eed"):
        if _measure(w[:-3]) > 0:
            w = w[:-1]
    elif w.endswith("ed") and _has_vowel(w[:-2]):
        w = w[:-2]
        flag = True
    elif w.endswith("ing") and _has_vowel(w[:-3]):
        w = w[:-3]
        flag = True
    if flag:
        if w.endswith(("at", "bl", "iz")):
            w += "e"
        elif _ends_double_cons(w) and not w.endswith(("l", "s", "z")):
            w = w[:-1]
        elif _measure(w) == 1 and _cvc(w):
            w += "e"

    # step 1c
    if w.endswith("y") and _has_vowel(w[:-1]):
        w = w[:-1] + "i"

    for suf, rep in _STEP2:
        if w.endswith(suf):
            if _measure(w[: -len(suf)]) > 0:
                w = w[: -len(suf)] + rep
            break

    for suf, rep in _STEP3:
        if w.endswith(suf):
            if _measure(w[: -len(suf)]) > 0:
                w = w[: -len(suf)] + rep
            break

    if w.endswith("ion") and len(w) > 3 and w[-4] in "st" and _measure(w[:-3]) > 1:
        w = w[:-3]
    else:
        for suf in _STEP4:
            if w.endswith(suf):
                stem = w[: -len(suf)]
                if _measure(stem) > 1:
                    w = stem
                break

    # step 5a
    if w.endswith("e"):
        stem = w[:-1]
        m = _measure(stem)
        if m > 1 or (m == 1 and not _cvc(stem)):
            w = stem
    # step 5b
    if _measure(w) > 1 and _ends_double_cons(w) and w.endswith("l"):
        w = w[:-1]
    return w


def porter_stem_filter(tokens: list[str]) -> list[str]:
    return [porter_stem(t) for t in tokens]


TOKEN_FILTERS: dict[str, Callable] = {
    "lowercase": lowercase_filter,
    "uppercase": uppercase_filter,
    "stop": stop_filter(),
    "asciifolding": asciifolding_filter,
    "porter_stem": porter_stem_filter,
    "stemmer": porter_stem_filter,
    "unique": unique_filter,
}

# parameterized factories for custom components declared under
# analysis.tokenizer.<name>.* / analysis.filter.<name>.* settings
# (ref: AnalysisModule registering *TokenizerFactory / *TokenFilterFactory)
TOKENIZER_FACTORIES: dict[str, Callable] = {
    "ngram": lambda s: ngram_tokenizer(s.get_int("min_gram", 1),
                                       s.get_int("max_gram", 2)),
    "nGram": lambda s: ngram_tokenizer(s.get_int("min_gram", 1),
                                       s.get_int("max_gram", 2)),
    "pattern": lambda s: pattern_tokenizer(s.get_str("pattern", r"\W+")),
    "standard": lambda s: standard_tokenizer,
    "whitespace": lambda s: whitespace_tokenizer,
    "letter": lambda s: letter_tokenizer,
    "keyword": lambda s: keyword_tokenizer,
}
def _resolve_stopwords(spec) -> frozenset:
    """`stopwords` setting -> concrete set: a list of words (each
    possibly a `_lang_` named set), one `_lang_` name, `_none_`, or
    absent -> English (ref: Analysis.parseStopWords resolving
    namedStopWords)."""
    if spec is None or spec in ("", "_english_"):
        return ENGLISH_STOP_WORDS
    if spec == "_none_" or spec == []:
        return frozenset()   # stopwords: [] means explicitly none
    from .lang_analysis import STOPWORDS
    names = spec if isinstance(spec, (list, tuple)) else [spec]
    out: set[str] = set()
    for n in names:
        n = str(n)
        if n.startswith("_") and n.endswith("_"):
            lang = n.strip("_")
            if lang == "none":
                continue
            if lang == "english":
                out |= ENGLISH_STOP_WORDS
                continue
            if lang not in STOPWORDS:
                raise IllegalArgumentError(
                    f"unknown named stopword set [{n}]")
            out |= STOPWORDS[lang]
        else:
            out.add(n)
    return frozenset(out)


FILTER_FACTORIES: dict[str, Callable] = {
    "stop": lambda s: stop_filter(_resolve_stopwords(
        s.get_list("stopwords", None))),
    "length": lambda s: length_filter(s.get_int("min", 0),
                                      s.get_int("max", 1 << 30)),
    "edge_ngram": lambda s: edge_ngram_filter(s.get_int("min_gram", 1),
                                              s.get_int("max_gram", 8)),
    "edgeNGram": lambda s: edge_ngram_filter(s.get_int("min_gram", 1),
                                             s.get_int("max_gram", 8)),
}

# ---------------------------------------------------------------------------
# Analyzers
# ---------------------------------------------------------------------------


class Analyzer:
    """A tokenizer + ordered filter chain."""

    def __init__(self, name: str, tokenizer: Callable, filters: list[Callable]):
        self.name = name
        self.tokenizer = tokenizer
        self.filters = filters

    def analyze(self, text: str) -> list[str]:
        tokens = self.tokenizer(text)
        for f in self.filters:
            tokens = f(tokens)
        return tokens

    def analyze_batch(self, texts: list[str]) -> list[list[str]]:
        return [self.analyze(t) for t in texts]

    def __repr__(self) -> str:
        return f"Analyzer({self.name!r})"


class _NativeBackedAnalyzer(Analyzer):
    """Standard analyzer with the C++ fast path (native/tokenizer.py);
    falls back to the Python chain when the toolchain is missing. Output
    parity is covered by tests/test_native.py."""

    def __init__(self):
        super().__init__("standard", standard_tokenizer, [lowercase_filter])
        self._native = None
        self._native_tried = False

    def _get_native(self):
        if not self._native_tried:
            self._native_tried = True
            try:
                from ..native.tokenizer import NativeStandardAnalyzer
                self._native = NativeStandardAnalyzer()
            except Exception:
                self._native = None
        return self._native

    def analyze(self, text: str) -> list[str]:
        nat = self._get_native()
        if nat is not None:
            return nat.analyze(text)
        return super().analyze(text)

    def analyze_batch(self, texts: list[str]) -> list[list[str]]:
        nat = self._get_native()
        if nat is not None:
            return nat.analyze_batch(texts)
        return super().analyze_batch(texts)


def _builtin_analyzers() -> dict[str, Analyzer]:
    return {
        "standard": _NativeBackedAnalyzer(),
        "simple": Analyzer("simple", letter_tokenizer, [lowercase_filter]),
        "whitespace": Analyzer("whitespace", whitespace_tokenizer, []),
        "keyword": Analyzer("keyword", keyword_tokenizer, []),
        "stop": Analyzer("stop", letter_tokenizer, [lowercase_filter, stop_filter()]),
        "english": Analyzer(
            "english", standard_tokenizer,
            [lowercase_filter, stop_filter(), porter_stem_filter]),
    }


# plugin-contributed whole analyzers, merged into every per-index
# service (ref: AnalysisModule.addAnalyzer — the extension point
# analysis plugins use; see plugins.py)
EXTRA_ANALYZERS: dict[str, "Analyzer"] = {}


def register_analyzer(name: str, analyzer) -> None:
    """Register a named analyzer globally. Accepts an Analyzer or a
    zero-arg factory returning one."""
    if callable(analyzer) and not isinstance(analyzer, Analyzer):
        analyzer = analyzer()
    if not isinstance(analyzer, Analyzer):
        raise IllegalArgumentError(
            f"plugin analyzer [{name}] must be an Analyzer")
    EXTRA_ANALYZERS[name] = analyzer


class AnalysisService:
    """Per-index registry of analyzers, built from index settings.

    Ref: index/analysis/AnalysisService.java — resolves named analyzers and
    custom chains declared under `analysis.analyzer.<name>.*` settings:

      analysis.analyzer.my_a.type: custom
      analysis.analyzer.my_a.tokenizer: standard
      analysis.analyzer.my_a.filter: ["lowercase", "stop"]
    """

    def __init__(self, settings: Settings = Settings.EMPTY):
        # index settings arrive in canonical "index."-prefixed form from
        # create-index (node.create_index normalization) and in bare
        # "analysis." form from direct construction — honor both
        stripped = settings.by_prefix("index.")
        if len(stripped):
            settings = settings.merged_with(stripped)
        self._analyzers = _builtin_analyzers()
        self._analyzers.update(EXTRA_ANALYZERS)  # plugin contributions
        # custom parameterized tokenizers/filters, then analyzers using them
        self._tokenizers = dict(TOKENIZERS)
        self._filters = dict(TOKEN_FILTERS)
        for name, group in settings.groups("analysis.tokenizer").items():
            typ = group.get_str("type") or ""
            factory = TOKENIZER_FACTORIES.get(typ)
            if factory is not None:
                self._tokenizers[name] = factory(group)
            elif typ in TOKENIZERS:  # parameterless builtin used as a type
                self._tokenizers[name] = TOKENIZERS[typ]
            else:
                raise IllegalArgumentError(f"unknown tokenizer type [{typ}] for [{name}]")
        for name, group in settings.groups("analysis.filter").items():
            typ = group.get_str("type") or ""
            factory = FILTER_FACTORIES.get(typ)
            if factory is not None:
                self._filters[name] = factory(group)
            elif typ in TOKEN_FILTERS:  # parameterless builtin used as a type
                self._filters[name] = TOKEN_FILTERS[typ]
            else:
                raise IllegalArgumentError(
                    f"unknown token filter type [{typ}] for [{name}]")
        for name, group in settings.groups("analysis.analyzer").items():
            self._analyzers[name] = self._build_custom(name, group)

    def _build_custom(self, name: str, s: Settings) -> Analyzer:
        typ = s.get_str("type", "custom")
        if typ != "custom":
            base = self._analyzers.get(typ)
            if base is None:
                raise IllegalArgumentError(f"unknown analyzer type [{typ}] for [{name}]")
            return Analyzer(name, base.tokenizer, list(base.filters))
        tok_name = s.get_str("tokenizer", "standard")
        tokenizer = self._tokenizers.get(tok_name)
        if tokenizer is None:
            raise IllegalArgumentError(f"unknown tokenizer [{tok_name}] for analyzer [{name}]")
        filters = []
        for f_name in s.get_list("filter", []) or []:
            f = self._filters.get(f_name)
            if f is None:
                raise IllegalArgumentError(f"unknown token filter [{f_name}] for analyzer [{name}]")
            filters.append(f)
        return Analyzer(name, tokenizer, filters)

    def analyzer(self, name: str) -> Analyzer:
        a = self._analyzers.get(name)
        if a is None:
            raise IllegalArgumentError(f"unknown analyzer [{name}]")
        return a

    @property
    def default_analyzer(self) -> Analyzer:
        return self._analyzers["standard"]

    def names(self) -> list[str]:
        return sorted(self._analyzers)


# language analyzers + stemmer/elision/normalization filters slot into
# the registries above (ref: the ~30 *AnalyzerProvider registrations in
# AnalysisModule)
from .lang_analysis import register_all as _register_languages  # noqa: E402
_register_languages()
