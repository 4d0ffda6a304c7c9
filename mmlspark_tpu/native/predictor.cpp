// Native single-row / small-batch predictor over the LightGBM v3 text
// model format — the serving-parity path SURVEY.md §7.1(c) prescribes
// where the reference scores single rows through its native booster
// (UPSTREAM: LightGBMBooster.score → LGBM_BoosterPredictForMatSingleRow,
// SURVEY.md §3.2 — [REF-EMPTY]).  The XLA predict path is optimal for
// batched DataFrame scoring but pays a dispatch round-trip per call;
// serving a single request wants a host-side walker with ~µs latency.
//
// Decision semantics mirror tests/test_golden_model.py's independent
// oracle (documented v3 rules): decision_type bit0 = categorical split,
// bit1 = default-left for missing; numerical goes left on value <=
// threshold; NaN on a categorical never matches the membership bitset;
// leaf references are -(k+1).  Leaf values already include shrinkage.
//
// Build: g++ -O3 -shared -fPIC -std=c++17 predictor.cpp -o _predictor-<sha12>.so
// (native/__init__.py load_native_lib names the binary after this source)
// (compiled on first use by mmlspark_tpu/native/__init__.py, ASAN pass in
// tests/test_native_binner.py's harness pattern).

#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

namespace {

struct Tree {
    int num_leaves = 1;
    std::vector<int> split_feature;
    std::vector<double> threshold;
    std::vector<int> decision_type;
    std::vector<int> left_child;
    std::vector<int> right_child;
    std::vector<double> leaf_value;
    std::vector<int> cat_boundaries;
    std::vector<uint32_t> cat_threshold;
};

struct Model {
    int num_class = 1;
    int num_tree_per_iteration = 1;
    int max_feature_idx = 0;
    // 0=identity/regression, 1=sigmoid, 2=softmax, 3=exp
    // (poisson/gamma/tweedie), 4=one-vs-all (sigmoid + normalize)
    int objective = 0;
    double sigmoid = 1.0;
    std::vector<Tree> trees;
};

bool starts_with(const std::string& s, const char* p) {
    return s.rfind(p, 0) == 0;
}

template <typename T, typename F>
void parse_list(const std::string& v, std::vector<T>& out, F conv) {
    out.clear();
    const char* p = v.c_str();
    char* end = nullptr;
    while (*p) {
        while (*p == ' ') ++p;
        if (!*p) break;
        out.push_back(static_cast<T>(conv(p, &end)));
        if (end == p) break;
        p = end;
    }
}

void parse_doubles(const std::string& v, std::vector<double>& out) {
    parse_list(v, out, [](const char* p, char** e) { return strtod(p, e); });
}
void parse_ints(const std::string& v, std::vector<int>& out) {
    parse_list(v, out, [](const char* p, char** e) { return strtol(p, e, 10); });
}
void parse_u32s(const std::string& v, std::vector<uint32_t>& out) {
    parse_list(v, out,
               [](const char* p, char** e) { return strtoul(p, e, 10); });
}

double score_tree(const Tree& t, const double* x, int64_t n_feat) {
    if (t.split_feature.empty()) {
        return t.leaf_value.empty() ? 0.0 : t.leaf_value[0];
    }
    int node = 0;
    for (;;) {
        const int f = t.split_feature[node];
        const double v = (f >= 0 && f < n_feat) ? x[f] : NAN;
        const int dt = t.decision_type[node];
        bool left;
        if (dt & 1) {  // categorical membership split
            // NaN or out-of-range category values are never members (the
            // range check also keeps the double->int64_t cast defined).
            if (!(v >= 0.0 && v < 2147483647.0)) {
                left = false;
            } else {
                const int ci = static_cast<int>(t.threshold[node]);
                const int lo = t.cat_boundaries[ci];
                const int hi = t.cat_boundaries[ci + 1];
                const int64_t c = static_cast<int64_t>(v);
                const int64_t w = c / 32, bit = c % 32;
                left = w < (hi - lo) &&
                       ((t.cat_threshold[lo + w] >> bit) & 1u);
            }
        } else if (std::isnan(v)) {
            left = (dt & 2) != 0;  // default direction
        } else {
            left = v <= t.threshold[node];
        }
        const int nxt = left ? t.left_child[node] : t.right_child[node];
        if (nxt < 0) return t.leaf_value[-nxt - 1];
        node = nxt;
    }
}

}  // namespace

extern "C" {

void* mml_model_load(const char* text) {
    auto* m = new Model();
    const char* p = text;
    Tree* cur = nullptr;
    bool in_trees_block = true;
    while (*p) {
        const char* nl = strchr(p, '\n');
        std::string line = nl ? std::string(p, nl - p) : std::string(p);
        p = nl ? nl + 1 : p + line.size();
        while (!line.empty() && (line.back() == '\r' || line.back() == ' '))
            line.pop_back();
        if (line.empty()) continue;
        if (starts_with(line, "end of trees")) {
            in_trees_block = false;
            continue;
        }
        if (!in_trees_block) continue;
        if (starts_with(line, "Tree=")) {
            m->trees.emplace_back();
            cur = &m->trees.back();
            continue;
        }
        const size_t eq = line.find('=');
        if (eq == std::string::npos) continue;
        const std::string k = line.substr(0, eq);
        const std::string v = line.substr(eq + 1);
        if (cur == nullptr) {  // header
            if (k == "num_class") m->num_class = atoi(v.c_str());
            else if (k == "num_tree_per_iteration")
                m->num_tree_per_iteration = atoi(v.c_str());
            else if (k == "max_feature_idx")
                m->max_feature_idx = atoi(v.c_str());
            else if (k == "objective") {
                if (starts_with(v, "binary")) {
                    m->objective = 1;
                    const size_t s = v.find("sigmoid:");
                    if (s != std::string::npos)
                        m->sigmoid = atof(v.c_str() + s + 8);
                } else if (starts_with(v, "multiclassova")) {
                    m->objective = 4;  // sigmoid per class, then normalize
                } else if (starts_with(v, "multiclass")) {
                    m->objective = 2;
                } else if (starts_with(v, "poisson") ||
                           starts_with(v, "gamma") ||
                           starts_with(v, "tweedie")) {
                    m->objective = 3;  // log-link: predict = exp(margin)
                }
            }
        } else {
            if (k == "num_leaves") cur->num_leaves = atoi(v.c_str());
            else if (k == "split_feature") parse_ints(v, cur->split_feature);
            else if (k == "threshold") parse_doubles(v, cur->threshold);
            else if (k == "decision_type") parse_ints(v, cur->decision_type);
            else if (k == "left_child") parse_ints(v, cur->left_child);
            else if (k == "right_child") parse_ints(v, cur->right_child);
            else if (k == "leaf_value") parse_doubles(v, cur->leaf_value);
            else if (k == "cat_boundaries") parse_ints(v, cur->cat_boundaries);
            else if (k == "cat_threshold") parse_u32s(v, cur->cat_threshold);
        }
    }
    // structural validation: a malformed tree must fail load, not walk
    for (const Tree& t : m->trees) {
        const size_t s = t.split_feature.size();
        if (t.threshold.size() != s || t.decision_type.size() != s ||
            t.left_child.size() != s || t.right_child.size() != s ||
            t.leaf_value.empty()) {
            delete m;
            return nullptr;
        }
        // cat_boundaries must be a non-negative non-decreasing prefix-sum
        // ending within cat_threshold (otherwise the bitset lookup reads
        // out of bounds)
        for (size_t i = 0; i + 1 < t.cat_boundaries.size(); ++i) {
            if (t.cat_boundaries[i] < 0 ||
                t.cat_boundaries[i] > t.cat_boundaries[i + 1]) {
                delete m;
                return nullptr;
            }
        }
        if (!t.cat_boundaries.empty() &&
            (t.cat_boundaries.front() < 0 ||
             t.cat_boundaries.back() >
                 static_cast<int>(t.cat_threshold.size()))) {
            delete m;
            return nullptr;
        }
        for (size_t i = 0; i < s; ++i) {
            const int l = t.left_child[i], r = t.right_child[i];
            // the v3 format numbers children AFTER their parent; a child
            // index <= its parent would let a malformed model cycle the
            // walker forever
            if ((l >= 0 && (l <= static_cast<int>(i) ||
                            l >= static_cast<int>(s))) ||
                (r >= 0 && (r <= static_cast<int>(i) ||
                            r >= static_cast<int>(s))) ||
                (l < 0 && -l - 1 >= static_cast<int>(t.leaf_value.size())) ||
                (r < 0 && -r - 1 >= static_cast<int>(t.leaf_value.size()))) {
                delete m;
                return nullptr;
            }
            if (t.decision_type[i] & 1) {
                const double ci = t.threshold[i];
                if (!(ci >= 0.0 &&
                      ci + 2 <= static_cast<double>(t.cat_boundaries.size()))) {
                    delete m;
                    return nullptr;
                }
            }
        }
    }
    return m;
}

void mml_model_info(void* h, int* num_class, int* num_trees,
                    int* max_feature_idx) {
    auto* m = static_cast<Model*>(h);
    *num_class = m->num_tree_per_iteration > 1 ? m->num_tree_per_iteration
                                               : m->num_class;
    *num_trees = static_cast<int>(m->trees.size());
    *max_feature_idx = m->max_feature_idx;
}

// out has n * K doubles (K = classes); raw=0 applies the objective
// transform (sigmoid / softmax), raw=1 returns margin sums.
void mml_model_predict(void* h, const double* X, int64_t n, int64_t n_feat,
                       int raw, double* out) {
    auto* m = static_cast<Model*>(h);
    const int K = m->num_tree_per_iteration > 1 ? m->num_tree_per_iteration
                                                : (m->num_class > 1 ? m->num_class : 1);
    for (int64_t i = 0; i < n; ++i) {
        double* o = out + i * K;
        for (int k = 0; k < K; ++k) o[k] = 0.0;
        const double* x = X + i * n_feat;
        for (size_t t = 0; t < m->trees.size(); ++t) {
            o[t % K] += score_tree(m->trees[t], x, n_feat);
        }
        if (!raw) {
            if (m->objective == 1) {
                for (int k = 0; k < K; ++k)
                    o[k] = 1.0 / (1.0 + std::exp(-m->sigmoid * o[k]));
            } else if (m->objective == 2) {
                double mx = o[0];
                for (int k = 1; k < K; ++k) mx = std::max(mx, o[k]);
                double sum = 0.0;
                for (int k = 0; k < K; ++k) {
                    o[k] = std::exp(o[k] - mx);
                    sum += o[k];
                }
                for (int k = 0; k < K; ++k) o[k] /= sum;
            } else if (m->objective == 3) {
                for (int k = 0; k < K; ++k) o[k] = std::exp(o[k]);
            } else if (m->objective == 4) {
                double sum = 0.0;
                for (int k = 0; k < K; ++k) {
                    o[k] = 1.0 / (1.0 + std::exp(-m->sigmoid * o[k]));
                    sum += o[k];
                }
                for (int k = 0; k < K; ++k) o[k] /= sum;
            }
        }
    }
}

void mml_model_free(void* h) { delete static_cast<Model*>(h); }

}  // extern "C"
