"""Transmogrifier — automatic per-type feature vectorization (reference:
core/.../stages/impl/feature/Transmogrifier.scala:92, the type-dispatch match
at :116-345, defaults at TransmogrifierDefaults:52-88, and the DSL
``.transmogrify()`` at dsl/RichFeaturesCollection.scala:69).

Groups input features by kind, applies the default vectorizer per group, and
combines all blocks with VectorsCombiner into one feature vector.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Type

from ..features import Feature
from ..types import (Base64, Binary, City, ComboBox, Country, Currency, Date,
                     DateList, DateTime, DateTimeList, Email, FeatureType,
                     Geolocation, ID, Integral, MultiPickList, OPMap, OPVector,
                     Percent, Phone, PickList, PostalCode, Real, RealNN, State,
                     Street, Text, TextArea, TextList, URL, is_map_kind)


class TransmogrifierDefaults:
    """≙ TransmogrifierDefaults (Transmogrifier.scala:52-88)."""

    DEFAULT_NUM_OF_FEATURES = 512
    MAX_NUM_OF_FEATURES = 16384
    TOP_K = 20
    MIN_SUPPORT = 10
    MAX_CATEGORICAL_CARDINALITY = 30
    FILL_VALUE = 0.0
    BINARY_FILL_VALUE = False
    TRACK_NULLS = True
    TRACK_INVALID = False
    TRACK_TEXT_LEN = False
    MIN_DOC_FREQUENCY = 0
    CIRCULAR_DATE_REPRESENTATIONS = ("HourOfDay", "DayOfWeek", "DayOfMonth", "DayOfYear")
    REFERENCE_DATE_MS = 1500000000000  # fixed anchor like joda's default


def _group_key(kind: Type[FeatureType]) -> str:
    if issubclass(kind, RealNN):
        return "realnn"
    if issubclass(kind, Binary):
        return "binary"
    if issubclass(kind, (Date, DateTime)):
        return "date"
    if issubclass(kind, Integral):
        return "integral"
    if issubclass(kind, (Real, Percent, Currency)):
        return "real"
    if issubclass(kind, (PickList, ComboBox, ID, Country, State, City,
                         PostalCode, Street)):
        return "categorical"
    if issubclass(kind, Email):
        return "email"
    if issubclass(kind, URL):
        return "url"
    if issubclass(kind, Phone):
        return "phone"
    if issubclass(kind, Base64):
        return "base64"
    if issubclass(kind, (TextArea, Text)):
        return "text"
    if issubclass(kind, TextList):
        return "textlist"
    if issubclass(kind, (DateList, DateTimeList)):
        return "datelist"
    if issubclass(kind, MultiPickList):
        return "multipicklist"
    if issubclass(kind, Geolocation):
        return "geolocation"
    if issubclass(kind, OPVector):
        return "vector"
    if is_map_kind(kind):
        return "map"
    raise TypeError(f"transmogrify: unsupported feature kind {kind.__name__}")


def transmogrify(features: Sequence[Feature],
                 top_k: int = TransmogrifierDefaults.TOP_K,
                 min_support: int = TransmogrifierDefaults.MIN_SUPPORT,
                 num_hashes: int = TransmogrifierDefaults.DEFAULT_NUM_OF_FEATURES,
                 max_categorical_cardinality: int = TransmogrifierDefaults.MAX_CATEGORICAL_CARDINALITY,
                 track_nulls: bool = TransmogrifierDefaults.TRACK_NULLS,
                 label: Optional[Feature] = None) -> Feature:
    """Auto-vectorize a heterogeneous feature list into one OPVector feature."""
    from .categorical import OneHotEstimator
    from .combiner import VectorsCombiner
    from .numeric import (BinaryVectorizer, IntegralVectorizer,
                          RealNNVectorizer, RealVectorizer)

    groups: Dict[str, List[Feature]] = {}
    for f in features:
        groups.setdefault(_group_key(f.kind), []).append(f)

    # specialized text kinds route through their validators/extractors first
    # (≙ TextTransmogrify cases, Transmogrifier.scala:116-180: email/url →
    # domain picklist, base64 → mime-type picklist, phone → isValid binary).
    # An email's or a url's domain is pivoted by the categorical pivot itself
    # (``OneHotEstimator``) from one native walk of the column,
    # so that no stage between the raw column and the pivot runs outside the
    # fused program
    from .text_specialized import IsValidPhoneDefaultCountry, MimeTypeDetector
    for group in ("email", "url"):
        groups.setdefault("categorical", []).extend(groups.pop(group, []))
    if not groups["categorical"]:
        del groups["categorical"]
    specialized_routes = [
        ("base64", MimeTypeDetector, "categorical"),
        ("phone", IsValidPhoneDefaultCountry, "binary"),
    ]
    for group, stage_cls, dest in specialized_routes:
        for f in groups.pop(group, []):
            st = stage_cls()
            st.set_input(f)
            groups.setdefault(dest, []).append(st.get_output())

    blocks: List[Feature] = []
    for key in sorted(groups):
        feats = groups[key]
        if key == "real":
            st = RealVectorizer(fill_mode="mean", track_nulls=track_nulls)
        elif key == "realnn":
            st = RealNNVectorizer()
        elif key == "integral":
            st = IntegralVectorizer(fill_mode="mode", track_nulls=track_nulls)
        elif key == "binary":
            st = BinaryVectorizer(track_nulls=track_nulls)
        elif key == "categorical":
            st = OneHotEstimator(top_k=top_k, min_support=min_support,
                                 track_nulls=track_nulls)
        elif key == "text":
            from .text import SmartTextVectorizer
            st = SmartTextVectorizer(
                max_cardinality=max_categorical_cardinality, top_k=top_k,
                min_support=min_support, num_hashes=num_hashes,
                track_nulls=track_nulls)
        elif key == "date":
            from .dates import DateToUnitCircleVectorizer
            st = DateToUnitCircleVectorizer(track_nulls=track_nulls)
        elif key == "datelist":
            from .dates import DateListVectorizer
            st = DateListVectorizer(track_nulls=track_nulls)
        elif key == "multipicklist":
            from .collections import MultiPickListVectorizer
            st = MultiPickListVectorizer(top_k=top_k, min_support=min_support,
                                         track_nulls=track_nulls)
        elif key == "textlist":
            from .text import TextListVectorizer
            st = TextListVectorizer(num_hashes=num_hashes)
        elif key == "geolocation":
            from .geo import GeolocationVectorizer
            st = GeolocationVectorizer(track_nulls=track_nulls)
        elif key == "map":
            # per-value-kind dispatch, mirroring the reference's per-map-type
            # cases (Transmogrifier.scala:142-217)
            from .map_vectorizers import (GeolocationMapVectorizer,
                                          MultiPickListMapVectorizer,
                                          SmartTextMapVectorizer,
                                          TextMapPivotVectorizer)
            from .maps import MapVectorizer
            from ..types import map_value_kind
            smart_text, pivot_text, multi, geo, generic = [], [], [], [], []
            for f in feats:
                vk = map_value_kind(f.kind)
                if issubclass(vk, (TextArea, Text)) and vk not in (
                        PickList, ComboBox, ID, Country, State, City,
                        PostalCode, Street, Email, URL, Phone, Base64):
                    smart_text.append(f)
                elif issubclass(vk, (PickList, ComboBox, ID, Country, State,
                                     City, PostalCode, Street, Email, URL,
                                     Phone, Base64)):
                    pivot_text.append(f)
                elif issubclass(vk, MultiPickList):
                    multi.append(f)
                elif issubclass(vk, Geolocation):
                    geo.append(f)
                else:
                    generic.append(f)
            if smart_text:
                st = SmartTextMapVectorizer(
                    max_cardinality=max_categorical_cardinality, top_k=top_k,
                    min_support=min_support, num_hashes=num_hashes,
                    track_nulls=track_nulls)
                st.set_input(*smart_text)
                blocks.append(st.get_output())
            if pivot_text:
                st = TextMapPivotVectorizer(top_k=top_k, min_support=min_support,
                                            track_nulls=track_nulls)
                st.set_input(*pivot_text)
                blocks.append(st.get_output())
            if multi:
                st = MultiPickListMapVectorizer(
                    top_k=top_k, min_support=min_support, track_nulls=track_nulls)
                st.set_input(*multi)
                blocks.append(st.get_output())
            if geo:
                st = GeolocationMapVectorizer(track_nulls=track_nulls)
                st.set_input(*geo)
                blocks.append(st.get_output())
            for f in generic:
                st = MapVectorizer(top_k=top_k, min_support=min_support,
                                   track_nulls=track_nulls)
                st.set_input(f)
                blocks.append(st.get_output())
            continue
        elif key == "vector":
            blocks.extend(feats)
            continue
        else:
            raise TypeError(f"transmogrify: no vectorizer for group {key}")
        st.set_input(*feats)
        blocks.append(st.get_output())

    combiner = VectorsCombiner()
    combiner.set_input(*blocks)
    return combiner.get_output()
