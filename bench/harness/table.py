"""The configuration's table, made from the run's seed: an extract of HM
Land Registry Price Paid with the release's own fields and value sets.

The container holds no copy of the release, so the rows are drawn from
the generator parameters in the configuration file (``table``), and the
schema (``schema``) is the release's: the price, the date of transfer,
the address fields (postcode, PAON, SAON, street, locality, town,
district, county) and the four coded fields (property type, old/new,
duration, PPD category).  Every field is a whole number: prices in whole
pounds, dates in days since 1 January 1995, each text field a code whose
0 is the empty string.  Addresses nest as in the release: a street lies
in one town, its postcodes on it, a town in one district, a district in
one county; the price is log-additive in the county, district, town,
street, property type, new build, tenure and PPD category, with noise.

``write`` puts the table where the program's ``read`` finds it
(``<lake>/<dataset>_<rows>_<table>.npy`` and ``.csv``), made anew in
every run."""

from __future__ import annotations

import os

import numpy as np

COLUMNS = ("price", "date", "postcode", "property_type", "old_new",
           "duration", "paon", "saon", "street", "locality", "town",
           "district", "county", "ppd_category")
PROPERTY_TYPES = ("D", "S", "T", "F", "O")


def cards(t: dict) -> dict:
    """Codes each field takes, the empty string's 0 included."""
    return {"postcode": 1 + t["streets"] * t["postcodes_per_street"],
            "property_type": len(PROPERTY_TYPES), "old_new": 2,
            "duration": 2,
            "paon": 1 + t["paon_numbers"] + t["paon_names"],
            "saon": 1 + t["saon_values"], "street": t["streets"],
            "locality": 1 + t["towns"] * t["localities_per_town"],
            "town": t["towns"], "district": t["districts"],
            "county": t["counties"], "ppd_category": 2}


def check_schema(config: dict) -> None:
    """The schema names the generator's columns, in order, with its
    codes; a configuration that disagrees is refused."""
    s = config["schema"]
    if tuple(s["names"]) != COLUMNS:
        raise ValueError(f"schema columns {s['names']} are not {COLUMNS}")
    want = cards(config["table"])
    for name, card in zip(s["names"], s["cards"]):
        if name in want and card != want[name]:
            raise ValueError(f"schema gives {name} {card} codes, the "
                             f"generator {want[name]}")


def generate(config: dict, seed: int) -> np.ndarray:
    """``(rows, 14)`` float64, the same for the same seed."""
    check_schema(config)
    t, n = config["table"], config["rows"]
    rng = np.random.default_rng([seed % (1 << 64), 0x5050])
    lp = t["log_price"]

    # the address hierarchy: towns in districts in counties, streets in
    # towns in proportion to the towns' sales
    T, D, C = t["towns"], t["districts"], t["counties"]
    town_share = 1.0 / np.arange(1, T + 1) ** t["town_zipf_s"]
    town_share = rng.permutation(town_share / town_share.sum())
    district_of = rng.permutation(np.arange(T) % D)
    county_of = rng.permutation(np.arange(D) % C)
    per_town = np.maximum(1, np.floor(town_share * t["streets"])).astype(
        np.int64)
    per_town[np.argmax(per_town)] += t["streets"] - per_town.sum()
    first_street = np.concatenate([[0], np.cumsum(per_town)[:-1]])

    town = rng.choice(T, n, p=town_share)
    district = district_of[town]
    county = county_of[district]
    street = first_street[town] + np.floor(
        rng.random(n) * per_town[town]).astype(np.int64)
    postcode = 1 + street * t["postcodes_per_street"] + rng.integers(
        0, t["postcodes_per_street"], n)
    postcode[rng.random(n) < t["postcode_missing_share"]] = 0
    L = t["localities_per_town"]
    locality = np.where(rng.random(n) < t["locality_share"],
                        1 + town * L + rng.integers(0, L, n), 0)

    shares = np.array([t["property_type_shares"][k]
                       for k in PROPERTY_TYPES])
    ptype = rng.choice(len(PROPERTY_TYPES), n, p=shares / shares.sum())
    flat = ptype == PROPERTY_TYPES.index("F")
    new = rng.random(n) < t["new_build_share"]
    lease = rng.random(n) < np.where(flat, t["leasehold_share"]["F"],
                                     t["leasehold_share"]["other"])
    ppd_b = rng.random(n) < t["ppd_b_share"]
    named = rng.random(n) < t["paon_name_share"]
    paon = np.where(named,
                    1 + t["paon_numbers"] + rng.integers(
                        0, t["paon_names"], n),
                    1 + np.minimum(rng.geometric(1.0 / t["paon_mean"], n)
                                   - 1, t["paon_numbers"] - 1))
    has_saon = rng.random(n) < np.where(flat, t["saon_share"]["F"],
                                        t["saon_share"]["other"])
    saon = np.where(has_saon, 1 + rng.integers(0, t["saon_values"], n), 0)
    date = t["first_day"] + rng.integers(0, t["days"], n)

    log_price = (lp["mean"]
                 + rng.normal(0, lp["county_sd"], C)[county]
                 + rng.normal(0, lp["district_sd"], D)[district]
                 + rng.normal(0, lp["town_sd"], T)[town]
                 + rng.normal(0, lp["street_sd"], t["streets"])[street]
                 + np.array([lp["type"][k] for k in PROPERTY_TYPES])[ptype]
                 + lp["new_build"] * new + lp["leasehold"] * lease
                 + lp["ppd_b"] * ppd_b
                 + rng.normal(0, 1, n) * np.where(ppd_b, lp["ppd_b_noise_sd"],
                                                  lp["noise_sd"]))
    price = np.maximum(np.round(np.exp(log_price)), 1.0)

    cols = {"price": price, "date": date, "postcode": postcode,
            "property_type": ptype, "old_new": new, "duration": lease,
            "paon": paon, "saon": saon, "street": street,
            "locality": locality, "town": town, "district": district,
            "county": county, "ppd_category": ppd_b}
    return np.stack([np.asarray(cols[c], dtype=np.float64)
                     for c in COLUMNS], axis=1)


def paths(lake: str, config: dict, table: int) -> tuple:
    stem = os.path.join(lake, f"{config['dataset']}_{config['rows']}_{table}")
    return stem + ".csv", stem + ".npy"


def write(config: dict, seed: int, lake: str) -> None:
    """Each of the configuration's tables, from the seed, as the binary
    file the native read maps and the CSV the python tier parses; every
    value is a whole number, so both read back the same float64."""
    os.makedirs(lake, exist_ok=True)
    for table in config["tables"]:
        X = generate(config, seed + table)
        csv_path, npy_path = paths(lake, config, table)
        with open(csv_path + ".tmp", "w") as f:
            f.write(",".join(COLUMNS) + "\n")
            np.savetxt(f, X, fmt="%d", delimiter=",")
        os.replace(csv_path + ".tmp", csv_path)
        np.save(npy_path + ".tmp.npy", X)
        os.replace(npy_path + ".tmp.npy", npy_path)
