"""Specialized text stages — the TPU-native re-design of the reference's
Lucene/OpenNLP/Tika/libphonenumber-backed feature family (reference:
core/.../stages/impl/feature/PhoneNumberParser.scala:143-258,
ValidEmailTransformer.scala:41, EmailToPickListMapTransformer.scala:40,
UrlMapToPickListMapTransformer.scala:40, MimeTypeDetector.scala:49-126,
OpCountVectorizer.scala:44, OpNGram.scala:52, OpStopWordsRemover.scala:48,
NGramSimilarity.scala:46-99, JaccardSimilarity.scala:40, LangDetector.scala:46,
NameEntityRecognizer.scala:56, HumanNameDetector.scala:56-118,
OpLDA.scala:41, OpWord2Vec.scala:41).

TPU design: string parsing/validation is a host-side vectorized prologue
(strings never reach the device — same split as ops/text.py); the numeric
products (count matrices, topic mixtures, embeddings) are device arrays, and
the LDA / Word2Vec training loops are jitted XLA programs (`lax.fori_loop`
over full-batch multiplicative updates / negative-sampling SGD steps) instead
of the reference's Spark MLlib wrappers.  Heavy external engines
(libphonenumber, Tika, Optimaize, OpenNLP) are replaced by compact built-in
tables: country calling-code metadata, magic-byte MIME signatures, per-language
stop-word profiles, and name/gender dictionaries.
"""

from __future__ import annotations

import base64
import binascii
import bisect
import functools
import re
from collections import Counter
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columns import Column, ColumnBatch
from ..stages.base import Estimator, Transformer, TransformerModel
from ..types import (Base64, Base64Map, Binary, BinaryMap, Email, EmailMap,
                     MultiPickList, MultiPickListMap, OPVector, Phone,
                     PhoneMap, PickList, PickListMap, Real, RealMap, RealNN,
                     Text, TextList, URL, URLMap)
from ..vector_meta import VectorColumnMeta, VectorMeta
from .categorical import _col_strings

# ---------------------------------------------------------------------------
# Email / URL validation
# ---------------------------------------------------------------------------

def email_parts(s: Optional[str]) -> Tuple[Optional[str], Optional[str]]:
    """(prefix, domain) of an email, Nones when invalid — delegates to the
    Email type accessors (types.py) for one set of semantics (≙ Email.prefix /
    Email.domain, features/.../types/Text.scala)."""
    if not s:
        return None, None
    e = Email(s)
    return e.prefix(), e.domain()


def url_domain(s: Optional[str]) -> Optional[str]:
    """Host of a valid http/https/ftp URL else None — delegates to the URL
    type accessors (≙ URL.domain/isValid, features/.../types/Text.scala:191)."""
    if not s:
        return None
    u = URL(s)
    return u.domain() if u.is_valid() else None


class ValidEmailTransformer(Transformer):
    """Email → Binary validity (≙ ValidEmailTransformer.scala:41: empty →
    empty Binary, else prefix and domain both non-empty)."""

    in_kinds = (Email,)
    out_kind = Binary
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        vals = np.zeros(len(strings), np.float32)
        mask = np.zeros(len(strings), bool)
        for i, s in enumerate(strings):
            if s is None:
                continue
            mask[i] = True
            p, d = email_parts(s)
            vals[i] = 1.0 if (p and d) else 0.0
        return Column(Binary, vals, mask=mask)


class EmailToPickListTransformer(Transformer):
    """Email → PickList of the domain (≙ EmailToPickListMapTransformer's inner
    EmailToPickList, EmailToPickListMapTransformer.scala:50-52)."""

    in_kinds = (Email,)
    out_kind = PickList
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        """The domains of one walk of the column (``ops.list_profile``)."""
        from .list_profile import domain_column, domain_values
        (f,) = self.input_features
        return domain_column(domain_values(batch[f.name], Email, f.name),
                             PickList)


class UrlToPickListTransformer(Transformer):
    """URL → PickList of the domain of a valid url (≙ the Transmogrifier's
    TextTransmogrify url case: url.toDomain, Transmogrifier.scala:116-180)."""

    in_kinds = (URL,)
    out_kind = PickList
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        """The hosts of one walk of the column (``ops.list_profile``)."""
        from .list_profile import domain_column, domain_values
        (f,) = self.input_features
        return domain_column(domain_values(batch[f.name], URL, f.name),
                             PickList)


class EmailMapToPickListMapTransformer(Transformer):
    """EmailMap → PickListMap of per-key domains (≙
    EmailToPickListMapTransformer.scala:40)."""

    in_kinds = (EmailMap,)
    out_kind = PickListMap
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        out = np.empty(len(batch), object)
        for i, m in enumerate(batch[f.name].values):
            m = m if isinstance(m, dict) else {}
            res = {}
            for k, v in m.items():
                _, d = email_parts(v)
                if d:
                    res[k] = d
            out[i] = res
        return Column(PickListMap, out)


class UrlMapToPickListMapTransformer(Transformer):
    """URLMap → PickListMap of per-key domains of *valid* urls (≙
    UrlMapToPickListMapTransformer.scala:40-44)."""

    in_kinds = (URLMap,)
    out_kind = PickListMap
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        out = np.empty(len(batch), object)
        for i, m in enumerate(batch[f.name].values):
            m = m if isinstance(m, dict) else {}
            res = {}
            for k, v in m.items():
                d = url_domain(v)
                if d:
                    res[k] = d
            out[i] = res
        return Column(PickListMap, out)


# ---------------------------------------------------------------------------
# Phone validation (≙ PhoneNumberParser.scala; libphonenumber replaced by a
# compact calling-code → national-number-length metadata table)
#
# Deliberate v1 trade-off: validation is LENGTH-ONLY per region/calling code.
# Unlike libphonenumber (the reference's 566-LoC wrapper + full metadata), we
# do not model digit-pattern rules, so these classes FALSE-ACCEPT:
#   * all-zero / reserved national numbers of a valid length
#     ("+1 000 000 0000" validates; libphonenumber rejects it),
#   * NANP numbers whose area code starts with 0/1,
#   * numbers in unlisted regions passed internationally with ``strict=False``
#     (any 4-15 digits after an unknown '+<cc>' are accepted, per E.164 shape).
# Rejections (wrong length for the matched calling code / default region,
# non-digit garbage, unknown default region) are reliable.  The envelope is
# pinned by tests/test_text_specialized.py::test_phone_validation_envelope.
# ---------------------------------------------------------------------------

# region → (calling code, min national digits, max national digits)
PHONE_REGIONS: Dict[str, Tuple[str, int, int]] = {
    "US": ("1", 10, 10), "CA": ("1", 10, 10), "GB": ("44", 9, 10),
    "FR": ("33", 9, 9), "DE": ("49", 6, 11), "ES": ("34", 9, 9),
    "IT": ("39", 8, 11), "NL": ("31", 9, 9), "BR": ("55", 10, 11),
    "MX": ("52", 10, 10), "IN": ("91", 10, 10), "CN": ("86", 10, 11),
    "JP": ("81", 9, 10), "KR": ("82", 8, 10), "AU": ("61", 9, 9),
    "RU": ("7", 10, 10), "ZA": ("27", 9, 9), "NG": ("234", 7, 10),
    "AR": ("54", 10, 10), "CL": ("56", 8, 9), "CO": ("57", 10, 10),
    "PE": ("51", 8, 9), "SE": ("46", 7, 9), "NO": ("47", 8, 8),
    "DK": ("45", 8, 8), "FI": ("358", 5, 10), "PL": ("48", 9, 9),
    "PT": ("351", 9, 9), "GR": ("30", 10, 10), "TR": ("90", 10, 10),
    "IL": ("972", 8, 9), "SA": ("966", 8, 9), "AE": ("971", 8, 9),
    "SG": ("65", 8, 8), "MY": ("60", 7, 10), "TH": ("66", 8, 9),
    "VN": ("84", 9, 10), "PH": ("63", 8, 10), "ID": ("62", 7, 11),
    "NZ": ("64", 8, 9), "IE": ("353", 7, 9), "CH": ("41", 9, 9),
    "AT": ("43", 4, 13), "BE": ("32", 8, 9), "CZ": ("420", 9, 9),
    "UA": ("380", 9, 9), "EG": ("20", 8, 10), "KE": ("254", 9, 9),
    "PK": ("92", 9, 10), "BD": ("880", 6, 10), "HK": ("852", 8, 8),
}

_CC_TO_RANGE: Dict[str, Tuple[int, int]] = {}
for _r, (_cc, _lo, _hi) in PHONE_REGIONS.items():
    lo, hi = _CC_TO_RANGE.get(_cc, (_lo, _hi))
    _CC_TO_RANGE[_cc] = (min(lo, _lo), max(hi, _hi))
_CCS_BY_LEN = sorted(_CC_TO_RANGE, key=len, reverse=True)

DEFAULT_REGION = "US"


def clean_phone_number(s: str) -> str:
    """Strip everything but digits and a leading '+'
    (≙ PhoneNumberParser.cleanNumber, PhoneNumberParser.scala:267)."""
    s = s.strip()
    plus = s.startswith("+")
    digits = re.sub(r"\D", "", s)
    return ("+" + digits) if plus else digits


def parse_phone(s: Optional[str], region: str = DEFAULT_REGION,
                strict: bool = False) -> Optional[str]:
    """→ E.164-ish '+<cc><national>' when valid, else None
    (≙ PhoneNumberParser.parse/validate, PhoneNumberParser.scala:270-320).
    International format (leading '+') is matched against known calling codes;
    otherwise the default region's metadata applies.  ``strict`` requires an
    exact length match even for international numbers with unknown codes."""
    if not s:
        return None
    cleaned = clean_phone_number(s)
    if cleaned.startswith("+"):
        digits = cleaned[1:]
        for cc in _CCS_BY_LEN:
            if digits.startswith(cc):
                lo, hi = _CC_TO_RANGE[cc]
                nat = digits[len(cc):]
                if lo <= len(nat) <= hi:
                    return "+" + digits
                return None
        return None if strict else ("+" + digits if 4 <= len(digits) <= 15 else None)
    meta = PHONE_REGIONS.get(region.upper())
    if meta is None:
        return None
    cc, lo, hi = meta
    digits = cleaned
    # national numbers sometimes carry the country code already
    if len(digits) > hi and digits.startswith(cc) and lo <= len(digits) - len(cc) <= hi:
        return "+" + digits
    if lo <= len(digits) <= hi:
        return "+" + cc + digits
    return None


class _PhoneParamsMixin:
    """≙ PhoneParams/PhoneCountryParams (PhoneNumberParser.scala:56-119)."""

    def set_default_region(self, cc: str):
        self.set("default_region", cc)
        return self

    def set_strictness(self, flag: bool):
        self.set("strict_validation", flag)
        return self


class ParsePhoneDefaultCountry(_PhoneParamsMixin, Transformer):
    """Phone → normalized E.164 Phone (≙ ParsePhoneDefaultCountry,
    PhoneNumberParser.scala:170-180)."""

    in_kinds = (Phone,)
    out_kind = Phone
    is_device_op = False

    def __init__(self, default_region: str = DEFAULT_REGION,
                 strict_validation: bool = False, **params):
        super().__init__(default_region=default_region,
                         strict_validation=strict_validation, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        out = np.empty(len(strings), object)
        for i, s in enumerate(strings):
            out[i] = parse_phone(s, self.get("default_region", DEFAULT_REGION),
                                 self.get("strict_validation", False))
        return Column(Phone, out)


class IsValidPhoneDefaultCountry(_PhoneParamsMixin, Transformer):
    """Phone → Binary validity (≙ IsValidPhoneDefaultCountry,
    PhoneNumberParser.scala:225-238)."""

    in_kinds = (Phone,)
    out_kind = Binary
    is_device_op = False

    def __init__(self, default_region: str = DEFAULT_REGION,
                 strict_validation: bool = False, **params):
        super().__init__(default_region=default_region,
                         strict_validation=strict_validation, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        vals = np.zeros(len(strings), np.float32)
        mask = np.zeros(len(strings), bool)
        for i, s in enumerate(strings):
            if s is None:
                continue
            mask[i] = True
            ok = parse_phone(s, self.get("default_region", DEFAULT_REGION),
                             self.get("strict_validation", False))
            vals[i] = 1.0 if ok else 0.0
        return Column(Binary, vals, mask=mask)


class IsValidPhoneMapDefaultCountry(_PhoneParamsMixin, Transformer):
    """PhoneMap → BinaryMap of per-key validity (≙ IsValidPhoneMapDefaultCountry,
    PhoneNumberParser.scala:241-251)."""

    in_kinds = (PhoneMap,)
    out_kind = BinaryMap
    is_device_op = False

    def __init__(self, default_region: str = DEFAULT_REGION,
                 strict_validation: bool = False, **params):
        super().__init__(default_region=default_region,
                         strict_validation=strict_validation, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        region = self.get("default_region", DEFAULT_REGION)
        strict = self.get("strict_validation", False)
        out = np.empty(len(batch), object)
        for i, m in enumerate(batch[f.name].values):
            m = m if isinstance(m, dict) else {}
            out[i] = {k: bool(parse_phone(v, region, strict))
                      for k, v in m.items() if v is not None}
        return Column(BinaryMap, out)


# ---------------------------------------------------------------------------
# MIME detection on Base64 (≙ MimeTypeDetector.scala; Tika replaced by
# magic-byte signatures)
# ---------------------------------------------------------------------------

_MAGIC: List[Tuple[bytes, str]] = [
    (b"\xff\xd8\xff", "image/jpeg"),
    (b"\x89PNG\r\n\x1a\n", "image/png"),
    (b"GIF87a", "image/gif"), (b"GIF89a", "image/gif"),
    (b"BM", "image/bmp"),
    (b"II*\x00", "image/tiff"), (b"MM\x00*", "image/tiff"),
    (b"%PDF", "application/pdf"),
    (b"PK\x03\x04", "application/zip"),
    (b"\x1f\x8b", "application/gzip"),
    (b"Rar!\x1a\x07", "application/x-rar-compressed"),
    (b"7z\xbc\xaf\x27\x1c", "application/x-7z-compressed"),
    (b"ID3", "audio/mpeg"), (b"\xff\xfb", "audio/mpeg"),
    (b"OggS", "audio/ogg"),
    (b"fLaC", "audio/flac"),
    (b"\x00\x00\x00\x18ftyp", "video/mp4"), (b"\x00\x00\x00\x20ftyp", "video/mp4"),
    (b"\x1aE\xdf\xa3", "video/webm"),
    (b"\xd0\xcf\x11\xe0\xa1\xb1\x1a\xe1", "application/x-ole-storage"),
    (b"{\\rtf", "application/rtf"),
    (b"MZ", "application/x-msdownload"),
    (b"\x7fELF", "application/x-elf"),
]


def detect_mime(data: bytes, type_hint: str = "") -> str:
    """Magic-byte MIME sniffing (≙ MimeTypeDetector.detect,
    MimeTypeDetector.scala:111-126).  ``type_hint`` wins when supplied, like
    Tika's CONTENT_TYPE hint."""
    if type_hint:
        return type_hint
    if data.startswith(b"RIFF") and len(data) >= 12:
        sub = data[8:12]
        if sub == b"WAVE":
            return "audio/x-wav"
        if sub == b"AVI ":
            return "video/x-msvideo"
        if sub == b"WEBP":
            return "image/webp"
    for sig, mime in _MAGIC:
        if data.startswith(sig):
            return mime
    head = data[:512].lstrip()
    low = head[:64].lower()
    if low.startswith(b"<?xml"):
        return "application/xml"
    if low.startswith(b"<!doctype html") or low.startswith(b"<html"):
        return "text/html"
    if not data:
        return "application/octet-stream"
    try:
        head.decode("utf-8")
        return "text/plain"
    except UnicodeDecodeError as e:
        # tolerate ONLY a genuine multi-byte char split by truncation: the
        # failing byte must be a UTF-8 lead byte whose continuation would
        # extend past the (cut) end — not just any junk near the end
        b0 = head[e.start]
        need = (2 if 0xC2 <= b0 <= 0xDF else 3 if 0xE0 <= b0 <= 0xEF
                else 4 if 0xF0 <= b0 <= 0xF4 else 0)
        if need and e.start + need > len(head) and e.start >= len(head) - 3:
            return "text/plain"
        return "application/octet-stream"


def _b64_bytes(s: Optional[str], max_bytes: int) -> Optional[bytes]:
    if s is None:
        return None
    # cut must stay a multiple of 4 so the truncated prefix is decodable
    cut = ((max_bytes + 2) // 3) * 4
    try:
        return base64.b64decode(s[:cut], validate=False)[:max_bytes]
    except (binascii.Error, ValueError):
        return b""


class MimeTypeDetector(Transformer):
    """Base64 → Text MIME type (≙ MimeTypeDetector.scala:49-57)."""

    in_kinds = (Base64,)
    out_kind = Text
    is_device_op = False

    def __init__(self, type_hint: str = "", max_bytes_to_parse: int = 1024,
                 **params):
        super().__init__(type_hint=type_hint,
                         max_bytes_to_parse=max_bytes_to_parse, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        out = np.empty(len(strings), object)
        hint = self.get("type_hint", "")
        mx = int(self.get("max_bytes_to_parse", 1024))
        for i, s in enumerate(strings):
            data = _b64_bytes(s, mx)
            out[i] = None if data is None else detect_mime(data, hint)
        return Column(Text, out)


class MimeTypeMapDetector(Transformer):
    """Base64Map → PickListMap of per-key MIME types (≙
    MimeTypeDetector.scala:61-70)."""

    in_kinds = (Base64Map,)
    out_kind = PickListMap
    is_device_op = False

    def __init__(self, type_hint: str = "", max_bytes_to_parse: int = 1024,
                 **params):
        super().__init__(type_hint=type_hint,
                         max_bytes_to_parse=max_bytes_to_parse, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        hint = self.get("type_hint", "")
        mx = int(self.get("max_bytes_to_parse", 1024))
        out = np.empty(len(batch), object)
        for i, m in enumerate(batch[f.name].values):
            m = m if isinstance(m, dict) else {}
            res = {}
            for k, v in m.items():
                data = _b64_bytes(v, mx)
                if data is not None:
                    res[k] = detect_mime(data, hint)
            out[i] = res
        return Column(PickListMap, out)


# ---------------------------------------------------------------------------
# CountVectorizer / NGram / StopWordsRemover (≙ Spark ML wrappers
# OpCountVectorizer.scala, OpNGram.scala, OpStopWordsRemover.scala)
# ---------------------------------------------------------------------------

class CountVectorizerModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        vocab: Dict[str, int] = {t: i for i, t in enumerate(self.fitted["vocab"])}
        n = len(batch)
        width = len(vocab)
        arr = np.zeros((n, width), np.float32)
        min_tf = float(self.get("min_tf", 1.0))
        binary = self.get("binary", False)
        for i, toks in enumerate(batch[f.name].values):
            if not toks:
                continue
            counts = Counter(t for t in toks if t in vocab)
            # minTF: per-document filter — fraction when < 1, else absolute
            thresh = min_tf * len(toks) if min_tf < 1.0 else min_tf
            for t, c in counts.items():
                if c >= thresh:
                    arr[i, vocab[t]] = 1.0 if binary else float(c)
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class OpCountVectorizer(Estimator):
    """TextList → count vector over a learned vocabulary (≙
    OpCountVectorizer.scala:44-121; Spark CountVectorizer semantics: vocab =
    top ``vocab_size`` terms with document frequency ≥ ``min_df``)."""

    in_kinds = (TextList,)
    out_kind = OPVector

    def __init__(self, vocab_size: int = 512, min_df: float = 1.0,
                 min_tf: float = 1.0, binary: bool = False, **params):
        super().__init__(vocab_size=vocab_size, min_df=min_df, min_tf=min_tf,
                         binary=binary, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        (f,) = self.input_features
        df_counts: Counter = Counter()
        tf_counts: Counter = Counter()
        n_docs = 0
        for toks in batch[f.name].values:
            if toks is None:
                continue
            n_docs += 1
            c = Counter(toks)
            tf_counts.update(c)
            df_counts.update(c.keys())
        min_df = float(self.get("min_df", 1.0))
        df_thresh = min_df * n_docs if min_df < 1.0 else min_df
        eligible = [t for t, d in df_counts.items() if d >= df_thresh]
        # top-vocab_size by total term frequency, ties broken lexicographically
        eligible.sort(key=lambda t: (-tf_counts[t], t))
        vocab = sorted(eligible[: int(self.get("vocab_size", 512))])
        cols = [VectorColumnMeta(f.name, f.kind.__name__, indicator_value=t)
                for t in vocab]
        meta = VectorMeta(self.output_name(), cols)
        return self._finalize_model(CountVectorizerModel(
            fitted={"vocab": vocab, "meta": meta}, **self.params))


class OpNGram(Transformer):
    """TextList → TextList of space-joined n-grams (≙ OpNGram.scala:52,
    Spark NGram semantics: fewer than n tokens → empty list)."""

    in_kinds = (TextList,)
    out_kind = TextList
    is_device_op = False

    def __init__(self, n: int = 2, **params):
        super().__init__(n=n, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        n = int(self.get("n", 2))
        out = np.empty(len(batch), object)
        for i, toks in enumerate(batch[f.name].values):
            toks = toks or []
            out[i] = [" ".join(toks[j:j + n]) for j in range(len(toks) - n + 1)]
        return Column(TextList, out)


# Spark ML's english stop-word list (StopWordsRemover.loadDefaultStopWords)
ENGLISH_STOP_WORDS: Set[str] = set("""a about above after again against all am
an and any are aren't as at be because been before being below between both
but by can't cannot could couldn't did didn't do does doesn't doing don't down
during each few for from further had hadn't has hasn't have haven't having he
he'd he'll he's her here here's hers herself him himself his how how's i i'd
i'll i'm i've if in into is isn't it it's its itself let's me more most
mustn't my myself no nor not of off on once only or other ought our ours
ourselves out over own same shan't she she'd she'll she's should shouldn't so
some such than that that's the their theirs them themselves then there there's
these they they'd they'll they're they've this those through to too under
until up very was wasn't we we'd we'll we're we've were weren't what what's
when when's where where's which while who who's whom why why's with won't
would wouldn't you you'd you'll you're you've your yours yourself
yourselves""".split())


class OpStopWordsRemover(Transformer):
    """TextList → TextList minus stop words (≙ OpStopWordsRemover.scala:48)."""

    in_kinds = (TextList,)
    out_kind = TextList
    is_device_op = False

    def __init__(self, stop_words: Optional[Sequence[str]] = None,
                 case_sensitive: bool = False, **params):
        super().__init__(stop_words=list(stop_words) if stop_words else None,
                         case_sensitive=case_sensitive, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        words = self.get("stop_words") or ENGLISH_STOP_WORDS
        cs = self.get("case_sensitive", False)
        stop = set(words) if cs else {w.lower() for w in words}
        out = np.empty(len(batch), object)
        for i, toks in enumerate(batch[f.name].values):
            toks = toks or []
            out[i] = [t for t in toks
                      if (t if cs else t.lower()) not in stop]
        return Column(TextList, out)


# ---------------------------------------------------------------------------
# N-gram / Jaccard similarity (≙ NGramSimilarity.scala, JaccardSimilarity.scala)
# ---------------------------------------------------------------------------

def ngram_distance(source: str, target: str, n: int = 3) -> float:
    """Lucene NGramDistance: n-gram-windowed edit similarity in [0, 1].

    The row recurrence ``cur[i] = min(cur[i-1]+1, prev[i]+1, prev[i-1]+ec)``
    vectorizes per target position: with ``b[i] = min(prev[i]+1, prev[i-1]+ec)``
    the left-neighbor term is ``min_k<=i (b[k] + (i-k))``, a cumulative min of
    ``b - i`` — so each row is O(sl) numpy instead of a Python inner loop."""
    sl, tl = len(source), len(target)
    if sl == 0 or tl == 0:
        return 1.0 if sl == tl else 0.0
    if sl < n or tl < n:
        matches = sum(1 for a, b in zip(source, target) if a == b)
        return matches / max(sl, tl)
    # source padded with n-1 sentinel chars; [sl, n] sliding n-gram windows
    sa = np.frombuffer(("\0" * (n - 1) + source).encode("utf-32-le"),
                       dtype=np.uint32)
    windows = np.lib.stride_tricks.sliding_window_view(sa, n)
    tgt = np.frombuffer(("\0" * (n - 1) + target).encode("utf-32-le"),
                        dtype=np.uint32)
    idx = np.arange(sl + 1, dtype=np.float64)
    prev = idx.copy()
    for j in range(1, tl + 1):
        t_j = tgt[j - 1:j - 1 + n]
        neq = windows != t_j
        cost = neq.sum(axis=1)
        # sentinel-prefix matches don't count toward the gram length
        tn = n - ((~neq) & (windows == 0)).sum(axis=1)
        ec = cost / tn
        b = np.empty(sl + 1, dtype=np.float64)
        b[0] = j
        np.minimum(prev[1:] + 1.0, prev[:-1] + ec, out=b[1:])
        prev = idx + np.minimum.accumulate(b - idx)
    return float(1.0 - prev[sl] / max(sl, tl))


class TextNGramSimilarity(Transformer):
    """(Text, Text) → RealNN n-gram similarity (≙ TextNGramSimilarity,
    NGramSimilarity.scala:62-99; either side empty → 0.0)."""

    in_kinds = (Text, Text)
    out_kind = RealNN
    is_device_op = False

    def __init__(self, ngram_size: int = 3, to_lowercase: bool = True, **params):
        super().__init__(ngram_size=ngram_size, to_lowercase=to_lowercase,
                         **params)

    def _to_string(self, v) -> str:
        if v is None:
            return ""
        if isinstance(v, (frozenset, set, list, tuple)):
            return " ".join(sorted(str(x) for x in v))
        return str(v)

    def transform(self, batch: ColumnBatch) -> Column:
        f1, f2 = self.input_features
        a = batch[f1.name].values
        b = batch[f2.name].values
        lc = self.get("to_lowercase", True)
        nsz = int(self.get("ngram_size", 3))
        vals = np.zeros(len(batch), np.float32)
        for i in range(len(batch)):
            s1, s2 = self._to_string(a[i]).strip(), self._to_string(b[i]).strip()
            if lc:
                s1, s2 = s1.lower(), s2.lower()
            vals[i] = 0.0 if (not s1 or not s2) else ngram_distance(s1, s2, nsz)
        return Column(RealNN, vals)


class SetNGramSimilarity(TextNGramSimilarity):
    """(MultiPickList, MultiPickList) → RealNN (≙ SetNGramSimilarity,
    NGramSimilarity.scala:46: sets joined to strings first)."""

    in_kinds = (MultiPickList, MultiPickList)


class JaccardSimilarity(Transformer):
    """(MultiPickList, MultiPickList) → RealNN |∩|/|∪|; both empty → 1.0
    (≙ JaccardSimilarity.scala:40-47)."""

    in_kinds = (MultiPickList, MultiPickList)
    out_kind = RealNN
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        f1, f2 = self.input_features
        a = batch[f1.name].values
        b = batch[f2.name].values
        vals = np.zeros(len(batch), np.float32)
        for i in range(len(batch)):
            x = set(a[i] or ())
            y = set(b[i] or ())
            if not x and not y:
                vals[i] = 1.0
            else:
                vals[i] = len(x & y) / len(x | y)
        return Column(RealNN, vals)


# ---------------------------------------------------------------------------
# Language detection (≙ LangDetector.scala + the 69-language enum at
# utils/.../text/LanguageDetector.scala:59; Optimaize replaced by Unicode
# script analysis + per-language stop-word profiles)
#
# Two signals, like the reference's n-gram detector effectively combines:
#   1. the SCRIPT a character belongs to (Hangul → ko, Thai → th, ...) — for
#      single-language scripts this alone seals the call, and for
#      script-families (Latin, Cyrillic, Arabic, Devanagari, Hebrew, Han)
#      it restricts the candidate set;
#   2. stop-word profile hit rates WITHIN the candidate set (space-separated
#      scripts), or distinctive-character counts for Han (simplified vs
#      traditional Chinese, kana → Japanese).
# ---------------------------------------------------------------------------

def _lang_profiles() -> Dict[str, Set[str]]:
    """Packaged per-language stop-word profiles (67 languages) — loaded from
    the resources module, the analog of Optimaize's language profiles shipped
    in the reference's models module (see resources/__init__.py)."""
    from ..resources import lang_profiles
    return lang_profiles()


_WORD_RE = re.compile(r"[^\W\d_]+", re.UNICODE)

# script → (unicode ranges, candidate languages); None candidates = resolved
# via the word profiles of the family
_SCRIPTS: Dict[str, Tuple[Tuple[Tuple[int, int], ...], Tuple[str, ...]]] = {
    "latin": (((0x41, 0x5A), (0x61, 0x7A), (0xC0, 0x24F),
               (0x1E00, 0x1EFF)), ()),          # profiles decide
    "cyrillic": (((0x400, 0x4FF),), ("ru", "uk", "bg", "sr", "mk", "be")),
    "greek": (((0x370, 0x3FF), (0x1F00, 0x1FFF)), ("el",)),
    "hebrew": (((0x590, 0x5FF),), ("he", "yi")),
    "arabic": (((0x600, 0x6FF), (0x750, 0x77F)), ("ar", "fa", "ur", "ckb")),
    "devanagari": (((0x900, 0x97F),), ("hi", "mr", "ne")),
    "bengali": (((0x980, 0x9FF),), ("bn",)),
    "gurmukhi": (((0xA00, 0xA7F),), ("pa",)),
    "gujarati": (((0xA80, 0xAFF),), ("gu",)),
    "tamil": (((0xB80, 0xBFF),), ("ta",)),
    "telugu": (((0xC00, 0xC7F),), ("te",)),
    "kannada": (((0xC80, 0xCFF),), ("kn",)),
    "malayalam": (((0xD00, 0xD7F),), ("ml",)),
    "thai": (((0xE00, 0xE7F),), ("th",)),
    "khmer": (((0x1780, 0x17FF),), ("km",)),
    "hangul": (((0xAC00, 0xD7AF), (0x1100, 0x11FF), (0x3130, 0x318F)),
               ("ko",)),
    "kana": (((0x3040, 0x309F), (0x30A0, 0x30FF)), ("ja",)),
    "han": (((0x4E00, 0x9FFF), (0x3400, 0x4DBF)), ()),   # zh-cn/zh-tw/ja
}

# distinctive Han characters: simplified-only vs traditional-only forms
# (characters shared by both orthographies carry no signal and are excluded
# symmetrically — compute the overlap FIRST so neither set keeps a shared
# character)
_HAN_SIMPLIFIED = set("这个们来说时国会学对发经点吗里后见长门问马语书车")
_HAN_TRADITIONAL = set("這個們來說時國會學對發經點嗎裡後見長門問馬語書車")
_HAN_SHARED = _HAN_SIMPLIFIED & _HAN_TRADITIONAL
_HAN_SIMPLIFIED -= _HAN_SHARED
_HAN_TRADITIONAL -= _HAN_SHARED


def detectable_languages() -> Tuple[str, ...]:
    """Codes detection is resourced for — the word-profile languages plus
    the script-sealed ones; mirrors the reference's Language enum
    (ISO 639-1/-3 + the zh-cn/zh-tw split)."""
    script_only = {"zh-cn", "zh-tw", "ja", "ko", "th", "km"}
    return tuple(sorted(script_only | set(_lang_profiles())))


# flat sorted (lo, hi, script) boundaries: one bisect per lookup instead of
# a linear scan over every script's ranges (texts pay this per character)
_SCRIPT_BOUNDS = sorted(
    (lo, hi, script)
    for script, (ranges, _) in _SCRIPTS.items() for lo, hi in ranges)
_SCRIPT_LOS = [b[0] for b in _SCRIPT_BOUNDS]


@functools.lru_cache(maxsize=8192)
def _script_of(ch: str) -> Optional[str]:
    cp = ord(ch)
    i = bisect.bisect_right(_SCRIPT_LOS, cp) - 1
    if i >= 0:
        lo, hi, script = _SCRIPT_BOUNDS[i]
        if cp <= hi:
            return script
    return None


def _profile_scores(tokens: List[str], candidates: Optional[Set[str]]
                    ) -> Dict[str, float]:
    scores: Dict[str, float] = {}
    for lang, profile in _lang_profiles().items():
        if candidates is not None and lang not in candidates:
            continue
        hits = sum(1 for t in tokens if t in profile)
        if hits:
            scores[lang] = hits / len(tokens)
    return scores


def detect_languages(s: str) -> Dict[str, float]:
    """Language → confidence, normalized to sum 1 over detected languages
    (≙ LangDetector.transformFn semantics: empty/no-signal → empty map).

    Covers the reference enum's breadth (LanguageDetector.scala:59): 67
    word-profile languages across Latin/Cyrillic/Arabic/Devanagari/Hebrew
    scripts plus script-sealed CJK, Thai, Khmer, Korean, Greek and Indic
    languages and the zh-cn/zh-tw split via character forms."""
    # letters by script
    script_counts: Dict[str, int] = {}
    han_simp = han_trad = 0
    for ch in s:
        if not ch.isalpha():
            continue
        sc = _script_of(ch)
        if sc is None:
            continue
        script_counts[sc] = script_counts.get(sc, 0) + 1
        if sc == "han":
            if ch in _HAN_SIMPLIFIED:
                han_simp += 1
            elif ch in _HAN_TRADITIONAL:
                han_trad += 1
    total = sum(script_counts.values())
    if not total:
        return {}

    scores: Dict[str, float] = {}
    # tokens per script family (space-separated scripts only)
    tokens_by_script: Dict[str, List[str]] = {}
    for t in _WORD_RE.findall(s):
        sc = _script_of(t[0])
        if sc is not None:
            tokens_by_script.setdefault(sc, []).append(t.lower())

    kana = script_counts.get("kana", 0)
    for script, cnt in script_counts.items():
        frac = cnt / total
        ranges, candidates = _SCRIPTS[script]
        if script == "han":
            # kana anywhere → the Han characters are Japanese kanji
            if kana:
                scores["ja"] = scores.get("ja", 0.0) + frac
            elif han_trad > han_simp:
                scores["zh-tw"] = scores.get("zh-tw", 0.0) + frac
            else:
                scores["zh-cn"] = scores.get("zh-cn", 0.0) + frac
            continue
        if script == "kana":
            scores["ja"] = scores.get("ja", 0.0) + frac
            continue
        if len(candidates) == 1:
            lang = candidates[0]
            scores[lang] = scores.get(lang, 0.0) + frac
            continue
        # script family resolved by word profiles (latin: open candidate set)
        toks = tokens_by_script.get(script, [])
        fam = _profile_scores(toks, set(candidates) or None) if toks else {}
        fam_total = sum(fam.values())
        if fam_total:
            for lang, sc_ in fam.items():
                scores[lang] = scores.get(lang, 0.0) + frac * sc_ / fam_total
        elif candidates:
            # no stop-word hit: fall back to the family's most common
            # language (ambiguous-script default, like Optimaize's priors)
            lang = candidates[0]
            scores[lang] = scores.get(lang, 0.0) + frac
        # latin with no hits contributes nothing (no-signal)

    total_score = sum(scores.values())
    if not total_score:
        return {}
    return {k: v / total_score for k, v in sorted(scores.items(),
                                                  key=lambda kv: -kv[1])}


class LangDetector(Transformer):
    """Text → RealMap of language confidences (≙ LangDetector.scala:46-61)."""

    in_kinds = (Text,)
    out_kind = RealMap
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        out = np.empty(len(strings), object)
        for i, s in enumerate(strings):
            out[i] = {} if s is None else detect_languages(s)
        return Column(RealMap, out)


# ---------------------------------------------------------------------------
# Name detection / NER (≙ HumanNameDetector.scala, NameEntityRecognizer.scala;
# OpenNLP models replaced by dictionaries + heuristics)
# ---------------------------------------------------------------------------

class _LazyMapping:
    """Dict/set-like view over a packaged resource, loaded on first use
    (≙ OpenNLPModels' lazily-loaded model cache).  Supports the read API of
    the dict/set constants it replaces: ``in``, iteration, ``len``, ``get``,
    ``[]``, ``keys``/``items``, and set union/intersection."""

    def __init__(self, loader):
        self._loader = loader
        self._data = None

    def _load(self):
        if self._data is None:
            self._data = self._loader()
        return self._data

    def __contains__(self, item):
        return item in self._load()

    def __iter__(self):
        return iter(self._load())

    def __len__(self):
        return len(self._load())

    def __getitem__(self, key):
        return self._load()[key]

    def get(self, key, default=None):
        d = self._load()
        return d.get(key, default) if hasattr(d, "get") else default

    def keys(self):
        d = self._load()
        return d.keys() if hasattr(d, "keys") else iter(d)

    def items(self):
        return self._load().items()

    def __or__(self, other):
        return set(self._load()) | set(other)

    def __and__(self, other):
        return set(self._load()) & set(other)


def _load_gender():
    from ..resources import gender_dictionary
    return gender_dictionary()


def _load_names():
    from ..resources import name_dictionary
    return name_dictionary()


# first-name → gender dictionary (≙ NameDetectUtils.DefaultGenderDictionary)
GENDER_DICT = _LazyMapping(_load_gender)

# surname + first-name union (≙ NameDetectUtils.DefaultNameDictionary)
NAME_DICT = _LazyMapping(_load_names)


def _name_tokens(s: Optional[str]) -> List[str]:
    """Lower-cased word tokens with salutations stripped (≙ NameDetectUtils
    preprocessing: honorifics like 'Dr.'/'Mrs.' never count as name hits)."""
    if not s:
        return []
    from ..resources import honorifics
    hon = honorifics()
    return [t.lower() for t in re.findall(r"[A-Za-z']+", s)
            if t.lower() not in hon]


class HumanNameDetectorModel(TransformerModel):
    out_kind = Text  # actual kind: NameStats (TextMap subtype)
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        from ..types import NameStats
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        treat = self.fitted["treat_as_name"]
        out = np.empty(len(strings), object)
        for i, s in enumerate(strings):
            if not treat or s is None:
                out[i] = {}
                continue
            toks = _name_tokens(s)
            gender = "GenderNA"
            for t in toks:
                g = GENDER_DICT.get(t)
                if g:
                    gender = g
                    break
            out[i] = {"IsName": "true", "OriginalValue": s, "Gender": gender}
        return Column(NameStats, out)


class HumanNameDetector(Estimator):
    """Text → NameStats; fit decides whether the column is a name column by
    dictionary hit rate (≙ HumanNameDetector.scala:56-118: treatAsName from
    aggregated NameDetectStats, model emits IsName/OriginalValue/Gender)."""

    in_kinds = (Text,)
    out_kind = Text
    allow_label_as_input = False

    def __init__(self, name_threshold: float = 0.5, **params):
        super().__init__(name_threshold=name_threshold, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        from ..types import NameStats
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        hits = total = 0
        for s in strings:
            toks = _name_tokens(s)
            if not toks:
                continue
            total += 1
            if sum(1 for t in toks if t in NAME_DICT) / len(toks) >= 0.5:
                hits += 1
        frac = hits / total if total else 0.0
        treat = frac >= float(self.get("name_threshold", 0.5))
        model = HumanNameDetectorModel(
            fitted={"treat_as_name": bool(treat)}, **self.params)
        model.out_kind = NameStats
        model.metadata["treatAsName"] = bool(treat)
        model.metadata["predictedNameProb"] = frac
        return self._finalize_model(model)

    def out_kind_at(self, i: int):
        from ..types import NameStats
        return NameStats


class NameEntityRecognizer(Transformer):
    """Text → MultiPickListMap token → entity-tag sets (≙
    NameEntityRecognizer.scala:56-89; OpenNLP tagger replaced by a
    dictionary + capitalization heuristic tagging Person tokens)."""

    in_kinds = (Text,)
    out_kind = MultiPickListMap
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        strings = _col_strings(batch[f.name])
        out = np.empty(len(strings), object)
        for i, s in enumerate(strings):
            res: Dict[str, Set[str]] = {}
            if s:
                for tok in re.findall(r"[A-Za-z']+", s):
                    if tok[0].isupper() and tok.lower() in NAME_DICT:
                        res.setdefault(tok, set()).add("Person")
            out[i] = {k: frozenset(v) for k, v in res.items()}
        return Column(MultiPickListMap, out)


# ---------------------------------------------------------------------------
# LDA + Word2Vec — jitted XLA training loops (≙ OpLDA.scala wrapping Spark
# LDA, OpWord2Vec.scala wrapping Spark Word2Vec)
# ---------------------------------------------------------------------------

def _lda_em(counts: jnp.ndarray, k: int, iters: int, seed: int
            ) -> jnp.ndarray:
    """Full-batch multiplicative EM for topic-word probabilities on a dense
    doc-term count matrix.  One XLA program: `lax.fori_loop` over E/M matmul
    steps — the MXU does the work the reference delegates to Spark LDA."""
    n, v = counts.shape
    key = jax.random.PRNGKey(seed)
    topics = jax.random.uniform(key, (k, v), dtype=jnp.float32) + 0.1
    topics = topics / topics.sum(axis=1, keepdims=True)
    doc_topic = jnp.full((n, k), 1.0 / k, dtype=jnp.float32)

    def step(_, state):
        topics, doc_topic = state
        # E-step responsibilities via two matmuls; eps guards empty docs
        mix = doc_topic[:, :, None] * topics[None, :, :]          # [n,k,v]
        denom = mix.sum(axis=1, keepdims=True) + 1e-12
        resp = mix / denom                                        # [n,k,v]
        weighted = resp * counts[:, None, :]                      # [n,k,v]
        doc_topic = weighted.sum(axis=2) + 1e-3
        doc_topic = doc_topic / doc_topic.sum(axis=1, keepdims=True)
        topics = weighted.sum(axis=0) + 1e-3
        topics = topics / topics.sum(axis=1, keepdims=True)
        return topics, doc_topic

    topics, _ = jax.lax.fori_loop(0, iters, step, (topics, doc_topic))
    return topics


def _lda_infer(counts: jnp.ndarray, topics: jnp.ndarray, iters: int = 20
               ) -> jnp.ndarray:
    """Infer doc-topic mixtures for fixed topics (jitted E-step iterations)."""
    n = counts.shape[0]
    k = topics.shape[0]
    doc_topic = jnp.full((n, k), 1.0 / k, dtype=jnp.float32)

    def step(_, doc_topic):
        mix = doc_topic[:, :, None] * topics[None, :, :]
        denom = mix.sum(axis=1, keepdims=True) + 1e-12
        resp = (mix / denom * counts[:, None, :]).sum(axis=2) + 1e-3
        return resp / resp.sum(axis=1, keepdims=True)

    return jax.lax.fori_loop(0, iters, step, doc_topic)


class OpLDAModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        col = batch[f.name]
        counts = jnp.maximum(
            jnp.asarray(np.asarray(col.values, np.float32)), 0.0)
        topics = jnp.asarray(self.fitted["topics"])
        mix = _lda_infer(counts, topics)
        return Column(OPVector, mix, meta=self.fitted["meta"])


class OpLDA(Estimator):
    """OPVector (term counts) → OPVector topic mixture (≙ OpLDA.scala:41;
    Spark LDA replaced by a jitted full-batch EM on device)."""

    in_kinds = (OPVector,)
    out_kind = OPVector

    def __init__(self, k: int = 10, max_iter: int = 20, seed: int = 42,
                 **params):
        super().__init__(k=k, max_iter=max_iter, seed=seed, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        (f,) = self.input_features
        counts = jnp.maximum(
            jnp.asarray(np.asarray(batch[f.name].values, np.float32)), 0.0)
        k = int(self.get("k", 10))
        topics = _lda_em(counts, k, int(self.get("max_iter", 20)),
                         int(self.get("seed", 42)))
        cols = [VectorColumnMeta(f.name, f.kind.__name__,
                                 descriptor_value=f"topic_{i}")
                for i in range(k)]
        meta = VectorMeta(self.output_name(), cols)
        return self._finalize_model(OpLDAModel(
            fitted={"topics": np.asarray(topics), "meta": meta},
            **self.params))


def _w2v_train(centers: jnp.ndarray, contexts: jnp.ndarray,
               negatives: jnp.ndarray, vocab_size: int, dim: int,
               epochs: int, lr: float, seed: int) -> jnp.ndarray:
    """Skip-gram negative-sampling SGD, full-batch per epoch, jitted."""
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    emb = jax.random.normal(k1, (vocab_size, dim), jnp.float32) * 0.1
    ctx = jax.random.normal(k2, (vocab_size, dim), jnp.float32) * 0.1

    def loss_fn(params):
        emb, ctx = params
        ec = emb[centers]                       # [P, d]
        cc = ctx[contexts]                      # [P, d]
        nc = ctx[negatives]                     # [P, neg, d]
        pos = jax.nn.log_sigmoid(jnp.sum(ec * cc, axis=-1))
        neg = jax.nn.log_sigmoid(-jnp.einsum("pd,pnd->pn", ec, nc)).sum(-1)
        return -(pos + neg).mean()

    grad_fn = jax.grad(loss_fn)

    def step(_, params):
        g_emb, g_ctx = grad_fn(params)
        emb, ctx = params
        return emb - lr * g_emb, ctx - lr * g_ctx

    emb, _ = jax.lax.fori_loop(0, epochs, step, (emb, ctx))
    return emb


class OpWord2VecModel(TransformerModel):
    out_kind = OPVector
    is_device_op = False

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        vocab: Dict[str, int] = {t: i for i, t in enumerate(self.fitted["vocab"])}
        emb = np.asarray(self.fitted["embeddings"])
        dim = emb.shape[1]
        out = np.zeros((len(batch), dim), np.float32)
        for i, toks in enumerate(batch[f.name].values):
            ids = [vocab[t] for t in (toks or []) if t in vocab]
            if ids:
                out[i] = emb[ids].mean(axis=0)
        return Column(OPVector, jnp.asarray(out), meta=self.fitted["meta"])


class OpWord2Vec(Estimator):
    """TextList → averaged word embedding (≙ OpWord2Vec.scala:41; Spark
    Word2Vec replaced by jitted skip-gram negative sampling; transform
    averages in-vocab token vectors, Spark-style)."""

    in_kinds = (TextList,)
    out_kind = OPVector

    def __init__(self, vector_size: int = 100, min_count: int = 5,
                 window: int = 5, num_negatives: int = 5, epochs: int = 50,
                 lr: float = 0.1, seed: int = 42, **params):
        super().__init__(vector_size=vector_size, min_count=min_count,
                         window=window, num_negatives=num_negatives,
                         epochs=epochs, lr=lr, seed=seed, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        (f,) = self.input_features
        docs = [toks or [] for toks in batch[f.name].values]
        counts = Counter(t for d in docs for t in d)
        min_count = int(self.get("min_count", 5))
        vocab_list = sorted(t for t, c in counts.items() if c >= min_count)
        vocab = {t: i for i, t in enumerate(vocab_list)}
        dim = int(self.get("vector_size", 100))
        cols = [VectorColumnMeta(f.name, f.kind.__name__,
                                 descriptor_value=f"w2v_{i}")
                for i in range(dim)]
        meta = VectorMeta(self.output_name(), cols)
        if not vocab_list:
            model = OpWord2VecModel(
                fitted={"vocab": [], "meta": meta,
                        "embeddings": np.zeros((0, dim), np.float32)},
                **self.params)
            return self._finalize_model(model)
        window = int(self.get("window", 5))
        rng = np.random.default_rng(int(self.get("seed", 42)))
        centers, contexts = [], []
        for d in docs:
            ids = [vocab[t] for t in d if t in vocab]
            for i, c in enumerate(ids):
                for j in range(max(0, i - window), min(len(ids), i + window + 1)):
                    if j != i:
                        centers.append(c)
                        contexts.append(ids[j])
        if not centers:
            emb = np.zeros((len(vocab_list), dim), np.float32)
        else:
            n_neg = int(self.get("num_negatives", 5))
            negs = rng.integers(0, len(vocab_list),
                                size=(len(centers), n_neg))
            emb = np.asarray(_w2v_train(
                jnp.asarray(np.array(centers, np.int32)),
                jnp.asarray(np.array(contexts, np.int32)),
                jnp.asarray(negs.astype(np.int32)),
                len(vocab_list), dim, int(self.get("epochs", 50)),
                float(self.get("lr", 0.1)), int(self.get("seed", 42))))
        model = OpWord2VecModel(
            fitted={"vocab": vocab_list, "embeddings": emb, "meta": meta},
            **self.params)
        return self._finalize_model(model)
