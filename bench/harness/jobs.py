"""One job of the benchmark's traffic, and the pipeline it submits.

A job is a plain dict, so the generator, the driver and the plain
reference (which imports nothing of the program) all read the same thing:

    {"id": 12, "agent": "agent-3", "table": 0, "rows": 250000,
     "preproc": "manual" | "table_vectorizer", "te_smoothing": float,
     "model": "ridge" | "elasticnet" | "gbt_xgboost" | "gbt_lightgbm",
     "params": {...}, "enc_seed": 7, "cv_seed": int, "cv_k": 3}

``build_batch`` turns it into the ``PipelineBatch`` the program runs: the
paper's Section 6 pipeline (``repro.agents.aide.PipelineSpec.build``) over
the configuration's schema, with the encoders seeded by ``enc_seed`` and
the folds by ``cv_seed``.
"""

from __future__ import annotations

FIT_NAME = {"ridge": "ridge_fit", "elasticnet": "elasticnet_fit",
            "gbt_xgboost": "gbt_fit", "gbt_lightgbm": "gbt_fit"}


def estimator(job: dict) -> dict:
    """The ``cv_score`` estimator spec of a job."""
    est = {"name": FIT_NAME[job["model"]], **job["params"]}
    if job["model"].startswith("gbt_"):
        est["flavor"] = job["model"][len("gbt_"):]
    return est


def column_groups(schema: dict) -> dict:
    """Positions, within the feature block (every column but the target),
    of each kind of column, as the Section 6 preprocessing splits them."""
    kinds, cards = schema["kinds"], schema["cards"]
    feats = [i for i, k in enumerate(kinds) if k != "target"]
    groups = {"feats": feats,
              "target": kinds.index("target"),
              "numeric": [], "low": [], "high": [], "datetime": []}
    for pos, col in enumerate(feats):
        kind = kinds[col]
        if kind == "numeric":
            groups["numeric"].append(pos)
        elif kind == "datetime":
            groups["datetime"].append(pos)
        elif kind == "categorical":
            groups["low" if cards[col] <= 16 else "high"].append(pos)
    return groups


def build_sink(job: dict, schema: dict, dataset: str):
    """The job's pipeline as a lazy sink (imports the program)."""
    from repro import tabular as T

    g = column_groups(schema)
    feats, cards = g["feats"], schema["cards"]
    sd = {"names": tuple(schema["names"]), "kinds": tuple(schema["kinds"]),
          "cards": tuple(schema["cards"])}
    raw = T.read(dataset, job["rows"], seed=job["table"])
    y = T.project(raw, [g["target"]])
    X = T.project(raw, feats)
    seed = job["enc_seed"]
    if job["preproc"] == "table_vectorizer":
        Xv = T.table_vectorizer(X, sd, feats)
    else:
        parts = []
        if g["numeric"]:
            parts.append(T.scale(T.impute(T.project(X, g["numeric"]))))
        for i in g["high"]:
            col = T.project(X, [i])
            parts.append(T.target_encode(col, y, cards[feats[i]],
                                         smoothing=job["te_smoothing"],
                                         seed=seed))
            parts.append(T.string_encode(col, dim=16, seed=seed))
        parts.append(T.onehot(T.project(X, g["low"]),
                              [cards[feats[i]] for i in g["low"]]))
        for i in g["datetime"]:
            parts.append(T.datetime_encode(T.project(X, [i])))
        Xv = T.concat(parts)
    return T.cv_score(Xv, T.log1p(y), estimator(job), k=job["cv_k"],
                      seed=job["cv_seed"])


def build_batch(job: dict, schema: dict, dataset: str):
    from repro.core.fusion import PipelineBatch
    return PipelineBatch([build_sink(job, schema, dataset)],
                         [f"job{job['id']}"])
