"""What a configuration file may say under ``reduced``: nothing, or a cut
to one chip as the ``model-configs`` guide (section 4) allows it. A plain
function over plain dicts: ``manifest.validate`` leaves the contract's
rules to the driver, and this one is the tests' (``test_bench_manifest``).

A cut file has, beside ``reduced``:

    model        the sizes as they are run (where the file has no such
                 block, its top level: the layout the contract asks of a
                 model in the catalog), under the source's own key names
    published    the same keys as the source has them: every key that
                 ``reduced`` names is cut in ``model``, every other key of
                 ``published`` is equal there, so no width is ever cut
    deployment   ``chips_per_layer`` and ``how``: over how many chips each
                 layer is shared, and how; where the depth is cut also
                 ``layer_period`` and ``leading_dense_layers`` (as kept:
                 the leading dense layers count once), and
                 ``kept_layer_ids``: the published index of each kept
                 layer, in order, counted as the source counts its layers
                 (from ``layer_ids_from``, 0 or 1; 0 where not given)

Only counts may be named, and they keep to the guide's floors: a whole
period and at least four of the layers after the leading dense ones, at
least 8 routed experts, at least an eighth of the vocabulary. A name in
``reduced`` is a top-level key of ``published``. What it counts is told

    by path    a nested block is walked: each leaf that differs from
               ``published`` has to be a count itself, every other leaf is
               equal, and a complaint names the leaf (``block.leaf``);
    by shape   a list or a string, against the published depth: one entry
               (character) a published layer is a per-layer pattern, a
               strictly increasing list of layer indices is a list of
               layer ids; both go with the depth, and no other list does;
    by name    a plain number, from ``COUNTS``: its shape cannot tell a
               width from a count, so that table is closed.

What is kept of a pattern or an id list is the published one at
``kept_layer_ids``, which after the leading dense layers are consecutive.
A file needs them where it names an id list or a pattern that only its
shape tells; ``layer_types`` / ``mlp_layer_types`` alone pass without.

``COUNTS`` and the catalog (``model-configs/architectures.jsonl``, 88
rows, scanned in PR 37). Depth: ``num_hidden_layers`` (84 rows),
``num_layers`` (the 4 LongCat rows). Leading dense layers:
``first_k_dense_replace`` (35 rows, the DeepSeek / Kimi / GLM lineages),
``num_dense_layers`` (Trinity, LFM2), ``n_dense_first_layers`` (Motif-3).
Routed experts: ``n_routed_experts`` (45), ``num_experts`` (23),
``num_local_experts`` (granite-4.0-h, MiniMax, Keye-VL),
``moe_num_experts`` (step3, Yuan3.0), ``moe_num_primary_experts``
(SmallThinker: its routed experts; ``moe_num_active_primary_experts`` is
the experts a token and stays out). Heads held: ``num_attention_heads``
(88), ``num_key_value_heads`` (81), ``swa_num_attention_heads`` /
``swa_num_key_value_heads`` (dots3, Inkling, MiMo-V2: the window layers'
own head counts beside the global layers'). Left out on purpose: what
counts shared experts (``n_shared_experts``, ``num_shared_experts``),
experts a token (``num_experts_per_tok``, ``experts_top_k``, ...), groups
(``n_group``, ``topk_group``), prediction modules
(``num_nextn_predict_layers``, ``num_mtp_modules``, ``mtp_num_layers``),
zero-compute experts (``zero_expert_num``) and the heads of recurrent,
linear and indexer mixers (``mamba_num_heads``, ``linear_num_value_heads``,
``index_n_heads``, ...: dividing them also divides a state, which no file
here has argued); ``dense_mlp_idx`` (Inkling) because its row does not
say whether it is an index or a count; ``unpadded_vocab_size`` because it
is a setting of the tokenizer, not rows held.
"""

#: the plain numbers ``reduced`` may name, under the catalog's names, and
#: what each counts: the depth itself, layers of one kind, experts, rows,
#: heads. The two ``*layer_types`` names are per-layer patterns that pass
#: without ``kept_layer_ids``, as they did before shape told a pattern.
COUNTS = {
    "num_hidden_layers": "depth",
    "num_layers": "depth",
    "num_dense_layers": "layers",
    "first_k_dense_replace": "layers",
    "n_dense_first_layers": "layers",
    "layer_types": "layers",
    "mlp_layer_types": "layers",
    "num_experts": "experts",
    "n_routed_experts": "experts",
    "num_local_experts": "experts",
    "moe_num_experts": "experts",
    "moe_num_primary_experts": "experts",
    "vocab_size": "vocabulary",
    "num_attention_heads": "heads",
    "num_key_value_heads": "heads",
    "swa_num_attention_heads": "heads",
    "swa_num_key_value_heads": "heads",
}

MIN_LAYERS_AFTER_DENSE = 4
MIN_EXPERTS = 8
MIN_VOCABULARY_SHARE = 8        # an eighth


def _whole(value) -> bool:
    return isinstance(value, int) and not isinstance(value, bool)


def _number(value) -> bool:
    return isinstance(value, (int, float)) and not isinstance(value, bool)


def _layer_ids(value, first: int, depth: int) -> bool:
    """A strictly increasing list of layer indices of a model ``depth``
    layers deep whose first layer is number ``first``."""
    return (isinstance(value, list) and all(_whole(i) for i in value)
            and all(a < b for a, b in zip(value, value[1:]))
            and all(first <= i < first + depth for i in value))


def _span(ids: list) -> str:
    """``0-3`` for consecutive ids, the list itself otherwise."""
    if ids and ids == list(range(ids[0], ids[-1] + 1)):
        return f"{ids[0]}-{ids[-1]}"
    return repr(ids)


class _Layers:
    """The depth of a cut file, published and kept, and which published
    layers are kept; ``problems`` are what the deployment says wrongly
    about them. ``depth`` is None where ``published`` states none."""

    def __init__(self, sizes: dict, published: dict, deployment):
        deployment = deployment or {}
        key = next((k for k, kind in COUNTS.items()
                    if kind == "depth" and k in published), None)
        self.depth = published[key] if key else None
        self.kept_depth = sizes.get(key) if key else None
        self.first = deployment.get("layer_ids_from", 0)
        self.kept_ids = deployment.get("kept_layer_ids")
        self.problems = []
        self.unplaced = []      # named keys that need ``kept_layer_ids``
        self.refused = False    # ``kept_layer_ids`` given and malformed
        if self.first not in (0, 1):
            self.problems.append(
                "deployment.layer_ids_from says whether the source counts "
                f"its layers from 0 or from 1, not {self.first!r}")
            self.first = 0
        if self.kept_ids is None:
            return
        if not (_whole(self.depth) and _whole(self.kept_depth)
                and _layer_ids(self.kept_ids, self.first, self.depth)
                and len(self.kept_ids) == self.kept_depth):
            self.problems.append(
                "deployment.kept_layer_ids gives the published index of "
                f"each of the {self.kept_depth!r} kept layers, strictly "
                f"increasing and counted from {self.first}: "
                f"{self.kept_ids!r} does not")
            self.kept_ids, self.refused = None, True
            return
        dense = deployment.get("leading_dense_layers")
        if _whole(dense) and 0 <= dense < len(self.kept_ids):
            after = self.kept_ids[dense:]
            if after != list(range(after[0], after[0] + len(after))):
                self.problems.append(
                    f"deployment.kept_layer_ids {self.kept_ids!r}: the "
                    f"layers kept after the {dense} leading dense are "
                    "consecutive published layers, a whole period as "
                    f"published, not {after!r}")

    def shape(self, value):
        """``ids``, ``pattern`` or None for a published list or string.
        Ids are asked first: the one list that is both, every layer's own
        index, renumbers as ids do."""
        if not _whole(self.depth):
            return None
        if _layer_ids(value, self.first, self.depth):
            return "ids"
        if len(value) == self.depth and (isinstance(value, str) or not any(
                isinstance(v, (list, dict)) for v in value)):
            return "pattern"
        return None

    def held(self, path: str, kept, full) -> list:
        """Clauses 2 and 4 on one named list or string that differs from
        ``published``: at most one complaint."""
        shape = self.shape(full)
        if shape is None:
            return [f"only counts may be named: {path!r} is neither a "
                    f"per-layer pattern (one entry for each of the "
                    f"{self.depth!r} published layers) nor a list of layer "
                    "ids (strictly increasing, counted from "
                    f"deployment.layer_ids_from = {self.first}); no other "
                    "list or string goes with the depth"]
        first, depth = self.first, self.kept_depth
        if shape == "ids":
            if not (_whole(depth) and _layer_ids(kept, first, depth)):
                return [f"{path!r} keeps {kept!r}: a list of layer ids is "
                        "strictly increasing and its ids lie within "
                        f"{first}-{first + (depth or 0) - 1}, the {depth!r} "
                        "kept layers renumbered"]
        elif type(kept) is not type(full) or len(kept) != depth:
            return [f"{path!r} keeps {kept!r}: a per-layer pattern has one "
                    f"entry for each of the {depth!r} kept layers"]
        if self.kept_ids is None:
            # taken on trust under the table's two names alone, as it was
            # before shape told a pattern
            on_trust = (shape == "pattern" and COUNTS.get(
                path.rpartition(".")[2]) == "layers")
            if not on_trust and not self.refused:
                self.unplaced.append(path)
            return []
        if shape == "ids":
            want = [n + first for n, i in enumerate(self.kept_ids)
                    if i in full]
            what = (f"the published ids among layers {_span(self.kept_ids)}"
                    ", renumbered,")
        else:
            want = [full[i - first] for i in self.kept_ids]
            if isinstance(full, str):
                want = "".join(want)
            what = f"the published layers {_span(self.kept_ids)}"
        if kept != want:
            return [f"{path!r} keeps {kept!r}, {what} are {want!r}"]
        return []


def _leaves(path: str, kept, full):
    """``(dotted path, kept, published)`` of every leaf under a key: the
    key itself, or where both sides are blocks each leaf of either (None
    for one that a side lacks)."""
    if not (isinstance(kept, dict) and isinstance(full, dict)):
        yield path, kept, full
        return
    for key in list(full) + [k for k in kept if k not in full]:
        yield from _leaves(f"{path}.{key}", kept.get(key), full.get(key))


def problems(config: dict, entry: dict) -> list:
    """Every clause of the rule that ``config`` (a configuration file)
    breaks, as text that names the clause; ``entry`` is the
    configuration's entry in ``BENCHMARK.json``. Empty: the file holds."""
    reduced = config.get("reduced")
    if reduced != entry.get("reduced"):
        return [f"BENCHMARK.json disagrees: the entry's reduced is "
                f"{entry.get('reduced')!r}, the file's {reduced!r}"]
    if not reduced:
        return []
    bad = []
    deployment = config.get("deployment")
    if not isinstance(deployment, dict):
        bad.append("deployment missing: a cut file says over how many chips "
                   "each layer is shared (`chips_per_layer`) and `how`")
        deployment = None
    elif not (isinstance(deployment.get("chips_per_layer"), int)
              and deployment["chips_per_layer"] >= 1
              and deployment.get("how")):
        bad.append("deployment needs `chips_per_layer` (a whole number of "
                   "chips) and `how`")
    published = config.get("published")
    if not isinstance(published, dict):
        return bad + ["published missing: a cut file states the source's own "
                      "sizes in a `published` block"]
    sizes = config.get("model", config)
    layers = _Layers(sizes, published, deployment)
    bad += layers.problems

    for key in reduced:
        if key not in sizes or key not in published:
            bad.append(f"{key!r} is in reduced but not in both `model` and "
                       "`published`")
        elif sizes[key] == published[key]:
            bad.append(f"{key!r} is in reduced but not smaller than "
                       f"published: {sizes[key]!r} vs {published[key]!r}")
        else:
            for path, kept, full in _leaves(key, sizes[key], published[key]):
                if kept != full:
                    bad += _count(path, kept, full, layers, deployment)
    if layers.unplaced:
        bad.append("deployment needs `kept_layer_ids` (the published index "
                   "of each kept layer, in order): nothing else says which "
                   "layers " + ", ".join(map(repr, layers.unplaced))
                   + " keep")
    for key, value in published.items():
        if key not in reduced and sizes.get(key) != value:
            differing = [repr(path) for path, kept, full
                         in _leaves(key, sizes.get(key), value)
                         if kept != full]
            if differing == [repr(key)]:
                bad.append(f"{key!r} differs from published and is not in "
                           f"reduced: {sizes.get(key)!r} vs {value!r}")
            else:
                bad.append(f"{', '.join(differing)} differ from published "
                           f"and {key!r} is not in reduced")
    return bad


def _count(path: str, kept, full, layers: _Layers, deployment) -> list:
    """One named leaf that differs from ``published``: a count, cut and
    over its floor, or one complaint."""
    if isinstance(full, (list, str)):
        return layers.held(path, kept, full)
    name = path.rpartition(".")[2]
    if not _number(full) or name not in COUNTS:
        return [f"only counts may be named: {path!r} is a width or a "
                f"setting, not one of {sorted(COUNTS)}"]
    if not (_number(kept) and kept < full):
        return [f"{path!r} is in reduced but not smaller than published: "
                f"{kept!r} vs {full!r}"]
    return _under_floor(path, COUNTS[name], kept, full, deployment)


def _under_floor(key: str, kind: str, kept, full, deployment) -> list:
    """The guide's floors on one count that is cut."""
    if kind == "experts" and kept < MIN_EXPERTS:
        return [f"floor: {key} keeps {kept} routed experts, under "
                f"{MIN_EXPERTS}"]
    if kind == "vocabulary" and kept * MIN_VOCABULARY_SHARE < full:
        return [f"floor: {key} keeps {kept} of {full} rows, under an "
                "eighth of the vocabulary"]
    if kind == "depth" and deployment is not None:
        period = deployment.get("layer_period")
        dense = deployment.get("leading_dense_layers")
        if not (isinstance(period, int) and period >= 1
                and isinstance(dense, int) and dense >= 0):
            return ["deployment needs `layer_period` and "
                    "`leading_dense_layers` where the depth is cut"]
        need = max(period, MIN_LAYERS_AFTER_DENSE)
        if kept - dense < need:
            return [f"floor: {key} keeps {kept - dense} layers after the "
                    f"{dense} leading dense, under {need} (a whole period "
                    f"of {period}, and at least {MIN_LAYERS_AFTER_DENSE})"]
    return []
