// Native selector row-match engine for the TPU throttler's host control plane.
//
// Role (reference parity): the reference's affectedThrottles is a linear Go
// scan of every Throttle's selector per pod event (throttle_controller.go:
// 248-269, clusterthrottle_controller.go:272-298).  The Python index
// (kube_throttler_tpu/engine/index.py) materializes the [P,T] mask and
// recomputes one row per pod event; this library moves that row recompute —
// the only O(#throttles) scalar loop left on the host — into C++.
//
// Model: Python keeps authority over interning (label keys/values/namespaces
// → int32 ids), row/column allocation, and the general tier (selectors
// whose validation fails — exact error-confinement semantics stay in
// Python).  Each throttle column is compiled here to its selector terms
// (selector.selecterTerms[] OR-ed, each term an AND of requirements —
// throttle_selector.go:30-54; ClusterThrottle terms additionally AND a
// namespaceSelector, clusterthrottle_selector.go:112-141).  Requirements
// carry an operator: Eq (matchLabels) plus the full matchExpressions set
// In / NotIn / Exists / DoesNotExist (metav1.LabelSelectorRequirement).
// ktn_match_row evaluates one pod in a single call; columns flagged
// general are evaluated back in Python.
//
// Semantics mirrored exactly (see SelectorIndex._match_one):
//   - namespaced Throttle: pod.namespace must equal the throttle's namespace
//     (applies to general columns too — the gate short-circuits them).
//   - ClusterThrottle: a pod whose Namespace object is unknown never matches
//     (clusterthrottle_controller.go:273-276).
//   - OR of zero terms is false (empty selector matches nothing); a term
//     with zero requirements matches everything.
//
// Candidate pruning: a matchLabels term with at least one pod requirement
// can only match a pod that carries the term's FIRST (key,value) pair
// exactly, so columns are inverted-indexed by that pair.  ktn_match_row
// then evaluates only the columns reachable from the pod's own label pairs
// (plus an "always" list: general columns and terms with no pod
// requirements) instead of scanning all T columns.  Term EVALUATION drops
// from O(T) to O(candidates); the output buffers are still zeroed in O(T)
// per call (byte memsets — cheap constants that the [T]-sized output ABI
// requires).
//
// C ABI only (loaded via ctypes); no exceptions cross the boundary.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <unordered_map>
#include <utility>
#include <vector>

namespace {

// operator codes (shared contract with native/__init__.py)
enum Op : int32_t {
  OP_EQ = 0,             // matchLabels entry: label == vals[0]
  OP_IN = 1,             // label present and ∈ vals
  OP_NOT_IN = 2,         // label absent, or ∉ vals
  OP_EXISTS = 3,         // key present
  OP_DOES_NOT_EXIST = 4, // key absent
};

struct Req {
  int32_t key;
  int32_t op;
  std::vector<int32_t> vals;  // Eq: 1 entry; In/NotIn: ≥1; Exists/DNE: empty
};

struct Term {
  std::vector<Req> pod;  // pod-label requirements
  std::vector<Req> ns;   // namespace-label requirements (ClusterThrottle only)
};

struct Col {
  bool valid = false;
  bool general = false;  // evaluated by the Python general tier
  bool in_always = false;
  int32_t thr_ns = -1;   // required pod-namespace id (namespaced Throttle); -1 = cluster
  std::vector<Term> terms;
  std::vector<uint64_t> bucket_keys;  // inverted-index keys this col occupies
};

struct Engine {
  bool cluster = false;  // kind == clusterthrottle
  std::vector<Col> cols;
  // (key,value) pair of a term's first pod requirement → candidate columns
  std::unordered_map<uint64_t, std::vector<int32_t>> buckets;
  std::vector<int32_t> always;  // general cols + terms with no pod reqs
  std::vector<int64_t> stamp;   // per-col visited epoch (query-time dedup)
  int64_t epoch = 0;
};

uint64_t bucket_key(int32_t k, int32_t v) {
  return (static_cast<uint64_t>(static_cast<uint32_t>(k)) << 32) |
         static_cast<uint32_t>(v);
}

void unindex_col(Engine* e, int32_t c) {
  Col& col = e->cols[c];
  for (uint64_t k : col.bucket_keys) {
    auto it = e->buckets.find(k);
    if (it == e->buckets.end()) continue;
    auto& v = it->second;
    v.erase(std::remove(v.begin(), v.end(), c), v.end());
    if (v.empty()) e->buckets.erase(it);
  }
  col.bucket_keys.clear();
  if (col.in_always) {
    e->always.erase(std::remove(e->always.begin(), e->always.end(), c),
                    e->always.end());
    col.in_always = false;
  }
}

bool has_exact_req(const Term& t) {
  for (const Req& r : t.pod)
    if (r.op == OP_EQ || (r.op == OP_IN && r.vals.size() == 1)) return true;
  return false;
}

void index_col(Engine* e, int32_t c) {
  Col& col = e->cols[c];
  if (!col.valid) return;
  // a term with no EXACT pod requirement (Eq / single-value In) cannot be
  // bucketed by value — multi-In/NotIn/Exists/DoesNotExist/ns-only terms
  // must be evaluated for every pod
  bool always = col.general;
  for (const Term& t : col.terms) {
    if (!has_exact_req(t)) always = true;
  }
  if (always) {
    e->always.push_back(c);
    col.in_always = true;
    return;  // evaluated unconditionally — bucket entries would be dead
  }
  for (const Term& t : col.terms) {
    // bucket by the term's first EXACT pod requirement (Eq, or In with one
    // value): a pod lacking that (key,value) provably fails the term.
    // Every term has one here — termless/inexact terms joined the always
    // list above and returned.
    for (const Req& r : t.pod) {
      bool exact = (r.op == OP_EQ) || (r.op == OP_IN && r.vals.size() == 1);
      if (!exact) continue;
      uint64_t k = bucket_key(r.key, r.vals[0]);
      auto& v = e->buckets[k];
      if (std::find(v.begin(), v.end(), c) == v.end()) v.push_back(c);
      col.bucket_keys.push_back(k);
      break;
    }
  }
}

// All requirements satisfied by the (keys,vals) label set?  Label sets are
// small (a handful of entries), so a linear probe beats hashing.
// Semantics mirror LabelSelector.matches (api/types.py:303-322).
bool pairs_match(const std::vector<Req>& reqs, const int32_t* keys,
                 const int32_t* vals, int32_t n) {
  for (const Req& r : reqs) {
    int32_t label_val = 0;
    bool present = false;
    for (int32_t i = 0; i < n; ++i) {
      if (keys[i] == r.key) {
        present = true;
        label_val = vals[i];
        break;
      }
    }
    switch (r.op) {
      case OP_EQ:
        if (!present || label_val != r.vals[0]) return false;
        break;
      case OP_IN: {
        if (!present) return false;
        bool in = false;
        for (int32_t v : r.vals)
          if (v == label_val) { in = true; break; }
        if (!in) return false;
        break;
      }
      case OP_NOT_IN: {
        if (present) {
          for (int32_t v : r.vals)
            if (v == label_val) return false;
        }
        break;
      }
      case OP_EXISTS:
        if (!present) return false;
        break;
      case OP_DOES_NOT_EXIST:
        if (present) return false;
        break;
      default:
        return false;  // unknown op never compiles; defensive
    }
  }
  return true;
}

}  // namespace

extern "C" {

void* ktn_create(int32_t is_cluster) {
  Engine* e = new Engine();
  e->cluster = (is_cluster != 0);
  return e;
}

void ktn_destroy(void* h) { delete static_cast<Engine*>(h); }

void ktn_reserve(void* h, int32_t tcap) {
  Engine* e = static_cast<Engine*>(h);
  if (static_cast<int32_t>(e->cols.size()) < tcap) e->cols.resize(tcap);
}

namespace {
// Decode one side's nested CSR: term t's requirements are indices
// [term_off[t], term_off[t+1]) into (req_key, req_op, req_voff); each
// requirement r's values are req_vals[req_voff[r]..req_voff[r+1]).
void decode_reqs(std::vector<Req>* out, int32_t t, const int32_t* term_off,
                 const int32_t* req_key, const int32_t* req_op,
                 const int32_t* req_voff, const int32_t* req_vals) {
  for (int32_t r = term_off[t]; r < term_off[t + 1]; ++r) {
    Req req;
    req.key = req_key[r];
    req.op = req_op[r];
    for (int32_t v = req_voff[r]; v < req_voff[r + 1]; ++v)
      req.vals.push_back(req_vals[v]);
    out->push_back(std::move(req));
  }
}
}  // namespace

// Compile a column.  Both selector sides arrive as nested CSR (see
// decode_reqs); operator codes per the Op enum.
void ktn_set_col(void* h, int32_t col, int32_t thr_ns, int32_t n_terms,
                 const int32_t* pod_term_off, const int32_t* pod_req_key,
                 const int32_t* pod_req_op, const int32_t* pod_req_voff,
                 const int32_t* pod_req_vals, const int32_t* ns_term_off,
                 const int32_t* ns_req_key, const int32_t* ns_req_op,
                 const int32_t* ns_req_voff, const int32_t* ns_req_vals) {
  Engine* e = static_cast<Engine*>(h);
  if (col >= static_cast<int32_t>(e->cols.size())) e->cols.resize(col + 1);
  unindex_col(e, col);
  Col& c = e->cols[col];
  c.valid = true;
  c.general = false;
  c.thr_ns = thr_ns;
  c.terms.clear();
  c.terms.reserve(n_terms);
  for (int32_t t = 0; t < n_terms; ++t) {
    Term term;
    decode_reqs(&term.pod, t, pod_term_off, pod_req_key, pod_req_op,
                pod_req_voff, pod_req_vals);
    decode_reqs(&term.ns, t, ns_term_off, ns_req_key, ns_req_op, ns_req_voff,
                ns_req_vals);
    c.terms.push_back(std::move(term));
  }
  index_col(e, col);
}

// Column whose selector needs the Python general tier (selectors that fail
// validation — exact error-confinement semantics stay in Python; valid
// matchExpressions compile natively via ktn_set_col).  The namespace gate
// still applies natively.
void ktn_set_col_general(void* h, int32_t col, int32_t thr_ns) {
  Engine* e = static_cast<Engine*>(h);
  if (col >= static_cast<int32_t>(e->cols.size())) e->cols.resize(col + 1);
  unindex_col(e, col);
  Col& c = e->cols[col];
  c.valid = true;
  c.general = true;
  c.thr_ns = thr_ns;
  c.terms.clear();
  index_col(e, col);
}

void ktn_clear_col(void* h, int32_t col) {
  Engine* e = static_cast<Engine*>(h);
  if (col < static_cast<int32_t>(e->cols.size())) {
    unindex_col(e, col);
    e->cols[col] = Col{};
  }
}

int32_t ktn_num_cols(void* h) {
  return static_cast<int32_t>(static_cast<Engine*>(h)->cols.size());
}

// Evaluate one pod against all compiled columns.
//   pod_ns     — interned namespace id of the pod
//   ns_exists  — 1 iff the Namespace object is known (ClusterThrottle gate)
//   (pk,pv,np) — interned pod-label (key,value) pairs
//   (nk,nv,nn) — interned namespace-label pairs of the pod's namespace
//   out[c]         — 1 iff column c matches (0 for general columns)
//   general_out[c] — 1 iff Python must evaluate column c (gate passed)
// Both outputs must hold ktn_num_cols entries.
void ktn_match_row(void* h, int32_t pod_ns, int32_t ns_exists,
                   const int32_t* pk, const int32_t* pv, int32_t np,
                   const int32_t* nk, const int32_t* nv, int32_t nn,
                   uint8_t* out, uint8_t* general_out) {
  Engine* e = static_cast<Engine*>(h);
  const int32_t T = static_cast<int32_t>(e->cols.size());
  std::memset(out, 0, T);
  std::memset(general_out, 0, T);
  if (static_cast<int32_t>(e->stamp.size()) < T) e->stamp.resize(T, 0);
  const int64_t epoch = ++e->epoch;

  auto eval = [&](int32_t c) {
    if (e->stamp[c] == epoch) return;  // already evaluated this call
    e->stamp[c] = epoch;
    const Col& col = e->cols[c];
    if (!col.valid) return;
    if (!e->cluster) {
      if (col.thr_ns != pod_ns) return;
    } else if (!ns_exists) {
      return;
    }
    if (col.general) {
      general_out[c] = 1;
      return;
    }
    for (const Term& t : col.terms) {
      if (!pairs_match(t.pod, pk, pv, np)) continue;
      if (e->cluster && !pairs_match(t.ns, nk, nv, nn)) continue;
      out[c] = 1;
      break;
    }
  };

  // candidates: columns whose bucketing pair the pod actually carries,
  // plus the always list (general columns / no-pod-requirement terms) —
  // a term's first requirement unmatched ⇒ the term cannot match, so
  // non-candidates are provably non-matching
  for (int32_t c : e->always) eval(c);
  for (int32_t i = 0; i < np; ++i) {
    auto it = e->buckets.find(bucket_key(pk[i], pv[i]));
    if (it == e->buckets.end()) continue;
    for (int32_t c : it->second) eval(c);
  }
}

// ---------------------------------------------------------------------------
// Single-pod 4-step classification over K gathered throttle columns — the
// native tier of the serving hot path (devicestate.check_pod's host route on
// accelerator backends, where a per-decision device dispatch would cost a
// full dispatch round trip).  Semantics are a line-for-line mirror of
// devicestate._host_classify_rows / ops.check._classify_core (reference
// check_throttled_for, throttle_types.go:128-153):
//   1. pod alone exceeds threshold        → 3 (POD_EXCEEDS; onEqual=false)
//   2. persisted status.throttled flags   → 1 (ACTIVE)
//   3. used+reserved saturates threshold  → 1 (ACTIVE; step3_on_equal)
//   4. used+reserved+pod overflows        → 2 (INSUFFICIENT; on_equal)
//   else                                  → 0 (NOT_THROTTLED)
// Invalid columns (thr_valid=0) report -1 (NOT_AFFECTED).  Presence-mask
// algebra (absent ≠ zero, resource_amount.go:127-159) carried by the *_p
// byte arrays; a ~20-numpy-op Python pass measured ~50µs/kind per decision
// at 100k×10k, this loop runs the same K×R work in well under 1µs, so the
// caller may hold its snapshot lock across the call.
//
// Status codes are a shared contract with ops/check.py CHECK_* and the
// [T]/[T,R] state arrays are the row-major int64/bool staging planes of
// devicestate._KindState (second dim exactly R, C-contiguous).
//
// API shape: plane pointers are REGISTERED once into a handle
// (ktn_cls_create) and re-registered only when Python reallocates a
// staging array (capacity growth — logarithmic under the ladder policy).
// A flat per-call signature was measured first: 22 ctypes data_as
// conversions cost ~50µs/call in marshaling alone, erasing the win; the
// handle form leaves 8 scalar args ≈ µs-scale.

struct ClsPlanes {
  int32_t R;
  const uint8_t* thr_valid;
  const int64_t* thr_cnt; const uint8_t* thr_cnt_p;
  const int64_t* thr_req; const uint8_t* thr_req_p;
  const uint8_t* st_cnt; const uint8_t* st_fp; const uint8_t* st_t;
  const int64_t* used_cnt; const uint8_t* used_cnt_p;
  const int64_t* used_req; const uint8_t* used_req_p;
  const int64_t* res_cnt; const uint8_t* res_cnt_p;
  const int64_t* res_req; const uint8_t* res_req_p;
};

void* ktn_cls_create(
    int32_t R,
    const uint8_t* thr_valid,
    const int64_t* thr_cnt, const uint8_t* thr_cnt_p,
    const int64_t* thr_req, const uint8_t* thr_req_p,
    const uint8_t* st_cnt, const uint8_t* st_fp, const uint8_t* st_t,
    const int64_t* used_cnt, const uint8_t* used_cnt_p,
    const int64_t* used_req, const uint8_t* used_req_p,
    const int64_t* res_cnt, const uint8_t* res_cnt_p,
    const int64_t* res_req, const uint8_t* res_req_p) {
  return new ClsPlanes{R, thr_valid, thr_cnt, thr_cnt_p, thr_req, thr_req_p,
                       st_cnt, st_fp, st_t, used_cnt, used_cnt_p,
                       used_req, used_req_p, res_cnt, res_cnt_p,
                       res_req, res_req_p};
}

void ktn_cls_destroy(void* h) { delete static_cast<ClsPlanes*>(h); }

void ktn_cls_run(const void* h, int32_t K, const int32_t* cols,
                 const int64_t* pod_req, const uint8_t* pod_present,
                 int32_t on_equal, int32_t step3_on_equal, int8_t* out) {
  const ClsPlanes& p = *static_cast<const ClsPlanes*>(h);
  const int32_t R = p.R;
  auto cmp = [](int64_t u, int64_t t, bool oe) { return oe ? u >= t : u > t; };
  const bool oe = on_equal != 0, s3 = step3_on_equal != 0;
  for (int32_t k = 0; k < K; ++k) {
    const int32_t c = cols[k];
    if (!p.thr_valid[c]) {
      out[k] = -1;  // NOT_AFFECTED
      continue;
    }
    const int64_t off = static_cast<int64_t>(c) * R;
    const int64_t* trq = p.thr_req + off;
    const uint8_t* trp = p.thr_req_p + off;
    const uint8_t* sfp = p.st_fp + off;
    const uint8_t* sft = p.st_t + off;
    const int64_t* urq = p.used_req + off;
    const uint8_t* urp = p.used_req_p + off;
    const int64_t* rrq = p.res_req + off;
    const uint8_t* rrp = p.res_req_p + off;
    const int64_t au_cnt = p.used_cnt[c] + p.res_cnt[c];
    const bool au_cnt_present = p.used_cnt_p[c] || p.res_cnt_p[c];

    // step 1 (pod count is 1 and always present)
    bool exceeds = p.thr_cnt_p[c] && (1 > p.thr_cnt[c]);
    for (int32_t r = 0; !exceeds && r < R; ++r)
      exceeds = trp[r] && pod_present[r] && pod_req[r] > trq[r] && pod_req[r] != 0;
    if (exceeds) {
      out[k] = 3;  // POD_EXCEEDS
      continue;
    }
    // step 2
    bool active = p.st_cnt[c];
    for (int32_t r = 0; !active && r < R; ++r)
      active = sfp[r] && sft[r] && pod_present[r] && pod_req[r] != 0;
    // step 3
    if (!active)
      active = p.thr_cnt_p[c] && au_cnt_present && cmp(au_cnt, p.thr_cnt[c], s3);
    for (int32_t r = 0; !active && r < R; ++r)
      active = trp[r] && (urp[r] || rrp[r]) &&
               cmp(urq[r] + rrq[r], trq[r], s3) &&
               pod_present[r] && pod_req[r] != 0;
    if (active) {
      out[k] = 1;  // ACTIVE
      continue;
    }
    // step 4
    bool insufficient = p.thr_cnt_p[c] && cmp(au_cnt + 1, p.thr_cnt[c], oe);
    for (int32_t r = 0; !insufficient && r < R; ++r)
      insufficient = trp[r] && (urp[r] || rrp[r] || pod_present[r]) &&
                     cmp(urq[r] + rrq[r] + pod_req[r], trq[r], oe) &&
                     pod_present[r] && pod_req[r] != 0;
    out[k] = insufficient ? 2 : 0;  // INSUFFICIENT : NOT_THROTTLED
  }
}

}  // extern "C"
