#include "tpudf/parquet_footer.hpp"

#include <clocale>
#include <cwctype>
#include <locale.h>

#include <map>
#include <stdexcept>

namespace tpudf {
namespace parquet {

using thrift::Value;
using thrift::WireType;

namespace {

// Full-range code-point lowering via towlower_l pinned to a UTF-8 locale
// (deterministic regardless of the process LC_CTYPE, unlike the
// reference's bare towlower after mbstowcs — same mapping table, no
// locale surprise). Falls back to identity above ASCII only if the image
// has no UTF-8 locale at all.
wint_t lower_code_point(wint_t cp) {
  static locale_t loc = [] {
    locale_t l = newlocale(LC_CTYPE_MASK, "C.UTF-8", (locale_t)0);
    if (!l) l = newlocale(LC_CTYPE_MASK, "en_US.UTF-8", (locale_t)0);
    return l;
  }();
  if (loc) return towlower_l(cp, loc);
  // no UTF-8 locale in the image: keep at least the ASCII + Latin-1
  // floor the pre-locale implementation guaranteed (U+00D7 is the
  // multiplication sign, not a letter)
  if (cp < 0x80) return towlower(cp);
  if (cp >= 0xC0 && cp <= 0xDE && cp != 0xD7) return cp + 0x20;
  return cp;
}

}  // namespace

std::string utf8_to_lower(std::string const& in) {
  std::string out;
  out.reserve(in.size());
  size_t i = 0;
  while (i < in.size()) {
    unsigned char c = in[i];
    if (c < 0x80) {
      out.push_back(c >= 'A' && c <= 'Z' ? c + 32 : c);
      ++i;
      continue;
    }
    // Decode one UTF-8 sequence.
    uint32_t cp = 0;
    int extra = 0;
    if ((c & 0xE0) == 0xC0) {
      cp = c & 0x1F;
      extra = 1;
    } else if ((c & 0xF0) == 0xE0) {
      cp = c & 0x0F;
      extra = 2;
    } else if ((c & 0xF8) == 0xF0) {
      cp = c & 0x07;
      extra = 3;
    } else {
      throw std::invalid_argument("invalid character sequence");
    }
    if (i + extra >= in.size()) {
      throw std::invalid_argument("invalid character sequence");
    }
    for (int k = 1; k <= extra; ++k) {
      unsigned char cc = in[i + k];
      if ((cc & 0xC0) != 0x80) {
        throw std::invalid_argument("invalid character sequence");
      }
      cp = (cp << 6) | (cc & 0x3F);
    }
    i += extra + 1;
    // Full wide-char-range simple lowering — the reference's
    // unicode_to_lower goes through towlower for every code point
    // (NativeParquetJni.cpp:45-77), so Greek/Cyrillic/etc column names
    // case-fold identically under case-insensitive matching.
    cp = static_cast<uint32_t>(lower_code_point(static_cast<wint_t>(cp)));
    // Re-encode.
    if (cp < 0x80) {
      out.push_back(static_cast<char>(cp));
    } else if (cp < 0x800) {
      out.push_back(static_cast<char>(0xC0 | (cp >> 6)));
      out.push_back(static_cast<char>(0x80 | (cp & 0x3F)));
    } else if (cp < 0x10000) {
      out.push_back(static_cast<char>(0xE0 | (cp >> 12)));
      out.push_back(static_cast<char>(0x80 | ((cp >> 6) & 0x3F)));
      out.push_back(static_cast<char>(0x80 | (cp & 0x3F)));
    } else {
      out.push_back(static_cast<char>(0xF0 | (cp >> 18)));
      out.push_back(static_cast<char>(0x80 | ((cp >> 12) & 0x3F)));
      out.push_back(static_cast<char>(0x80 | ((cp >> 6) & 0x3F)));
      out.push_back(static_cast<char>(0x80 | (cp & 0x3F)));
    }
  }
  return out;
}

Footer Footer::parse(uint8_t const* buf, uint64_t len) {
  Footer footer(thrift::parse_struct(buf, len));
  if (Value const* groups = footer.meta_.field(fid::kRowGroups)) {
    for (size_t g = 0; g < groups->elems.size(); ++g) {
      footer.kept_groups_.push_back(static_cast<int32_t>(g));
    }
  }
  footer.file_leaves_ = static_cast<int32_t>(footer.leaves().size());
  return footer;
}

namespace {

// The requested-column tree, built depth-first from the JNI-shaped
// (names, num_children) request. s_id numbers nodes in request depth-first
// order (root = 0); c_id numbers leaves only.
struct RequestNode {
  std::map<std::string, RequestNode> children;
  int s_id = 0;
  int c_id = -1;
};

RequestNode build_request_tree(std::vector<std::string> const& names,
                               std::vector<int32_t> const& num_children,
                               int32_t parent_num_children) {
  RequestNode root;
  if (parent_num_children == 0) return root;
  if (names.size() != num_children.size()) {
    throw std::invalid_argument("names and num_children length mismatch");
  }
  int next_s = 0;
  int next_c = -1;
  std::vector<RequestNode*> stack{&root};
  std::vector<int32_t> remaining{parent_num_children};
  for (size_t k = 0; k < names.size(); ++k) {
    if (stack.empty()) {
      throw std::invalid_argument("request tree: too many entries");
    }
    ++next_s;
    RequestNode node;
    node.s_id = next_s;
    if (num_children[k] == 0) node.c_id = ++next_c;
    auto [it, _] = stack.back()->children.try_emplace(names[k], node);
    if (num_children[k] > 0) {
      stack.push_back(&it->second);
      remaining.push_back(num_children[k]);
    } else {
      while (!stack.empty() && --remaining.back() == 0) {
        stack.pop_back();
        remaining.pop_back();
      }
    }
  }
  if (!stack.empty()) {
    throw std::invalid_argument("request tree: not enough entries");
  }
  return root;
}

struct PruneMaps {
  std::vector<int> schema_gather;       // output schema pos -> input index
  std::vector<int> schema_num_children; // new num_children per output pos
  std::vector<int> chunk_gather;        // output chunk pos -> input leaf idx
  std::vector<int> chunk_request;       // output chunk pos -> request leaf id
};

// One pass over the flattened file schema, matching against the request
// tree. Same observable semantics as the reference's column_pruner
// (NativeParquetJni.cpp:122-303): missing requested columns leave gaps
// that are compressed out by the ordered maps.
PruneMaps compute_prune_maps(Value const& schema_list, RequestNode& request,
                             bool ignore_case) {
  auto const& elems = schema_list.elems;
  if (elems.empty()) {
    throw std::invalid_argument("a root schema element must exist");
  }
  std::map<int, int> schema_map;        // s_id -> input schema index
  std::map<int, int> num_children_map;  // s_id -> new num_children
  std::map<int, int> chunk_map;         // c_id -> input leaf index
  schema_map[0] = 0;
  num_children_map[0] = 0;

  std::vector<RequestNode*> stack{&request};
  Value const* root_nc = elems[0].field(fid::kSeNumChildren);
  std::vector<int64_t> remaining{root_nc ? root_nc->i : 0};

  int chunk_index = 0;
  for (size_t idx = 1; idx < elems.size() && !stack.empty(); ++idx) {
    Value const& se = elems[idx];
    Value const* name_f = se.field(fid::kSeName);
    std::string name = name_f ? name_f->bin : std::string();
    if (ignore_case) name = utf8_to_lower(name);
    Value const* nc_f = se.field(fid::kSeNumChildren);
    int64_t n_children = nc_f ? nc_f->i : 0;
    bool is_leaf = se.field(fid::kSeType) != nullptr;

    RequestNode* found = nullptr;
    if (stack.back() != nullptr) {
      auto it = stack.back()->children.find(name);
      if (it != stack.back()->children.end()) {
        found = &it->second;
        ++num_children_map[stack.back()->s_id];
        schema_map[found->s_id] = static_cast<int>(idx);
        num_children_map[found->s_id] = 0;
      }
    }
    if (is_leaf) {
      if (found != nullptr) chunk_map[found->c_id] = chunk_index;
      ++chunk_index;
    }
    if (n_children > 0) {
      stack.push_back(found);
      remaining.push_back(n_children);
    } else {
      while (!stack.empty() && --remaining.back() == 0) {
        stack.pop_back();
        remaining.pop_back();
      }
    }
  }

  PruneMaps maps;
  for (auto const& [_, v] : schema_map) maps.schema_gather.push_back(v);
  for (auto const& [_, v] : num_children_map) {
    maps.schema_num_children.push_back(v);
  }
  for (auto const& [c_id, v] : chunk_map) {
    maps.chunk_gather.push_back(v);
    maps.chunk_request.push_back(c_id);
  }
  return maps;
}

int64_t chunk_start_offset(Value const& chunk) {
  Value const* md = chunk.field(fid::kCcMetaData);
  if (md == nullptr) return 0;
  Value const* data_off = md->field(fid::kCmDataPageOffset);
  int64_t offset = data_off ? data_off->i : 0;
  Value const* dict_off = md->field(fid::kCmDictionaryPageOffset);
  if (dict_off != nullptr && offset > dict_off->i) offset = dict_off->i;
  return offset;
}

}  // namespace

void Footer::prune_columns(std::vector<std::string> const& names,
                           std::vector<int32_t> const& num_children,
                           int32_t parent_num_children, bool ignore_case) {
  Value* schema = meta_.field(fid::kSchema);
  if (schema == nullptr || schema->type != WireType::LIST) {
    throw std::invalid_argument("footer has no schema list");
  }
  RequestNode request =
      build_request_tree(names, num_children, parent_num_children);
  PruneMaps maps = compute_prune_maps(*schema, request, ignore_case);

  // Gather the schema, rewriting num_children where the element carries it
  // (leaves without the field stay without it, like the reference, whose
  // plain member assignment does not flip thrift's __isset flag).
  std::vector<Value> new_schema;
  new_schema.reserve(maps.schema_gather.size());
  for (size_t out = 0; out < maps.schema_gather.size(); ++out) {
    Value se = schema->elems[maps.schema_gather[out]];
    if (Value* nc = se.field(fid::kSeNumChildren)) {
      nc->i = maps.schema_num_children[out];
    }
    new_schema.push_back(std::move(se));
  }
  schema->elems = std::move(new_schema);

  // Gather column_orders by leaf position.
  if (Value* orders = meta_.field(fid::kColumnOrders)) {
    std::vector<Value> new_orders;
    new_orders.reserve(maps.chunk_gather.size());
    for (int src : maps.chunk_gather) {
      if (src < 0 || static_cast<size_t>(src) >= orders->elems.size()) continue;
      new_orders.push_back(orders->elems[src]);
    }
    orders->elems = std::move(new_orders);
  }

  chunk_gather_ = std::move(maps.chunk_gather);
  chunk_request_ = std::move(maps.chunk_request);
  pruned_ = true;
}

void Footer::filter_columns() {
  if (!pruned_) {
    throw std::logic_error("filter_columns requires prune_columns first");
  }
  Value* groups = meta_.field(fid::kRowGroups);
  if (groups == nullptr) return;
  for (Value& rg : groups->elems) {
    Value* cols = rg.field(fid::kRgColumns);
    if (cols == nullptr) continue;
    std::vector<Value> new_cols;
    new_cols.reserve(chunk_gather_.size());
    for (int src : chunk_gather_) {
      if (src < 0 || static_cast<size_t>(src) >= cols->elems.size()) {
        throw std::out_of_range("chunk index outside row group columns");
      }
      new_cols.push_back(cols->elems[src]);
    }
    cols->elems = std::move(new_cols);
  }
}

void Footer::filter_row_groups(int64_t part_offset, int64_t part_length) {
  if (part_length < 0) return;  // reference gate: NativeParquetJni.cpp:542
  Value* groups = meta_.field(fid::kRowGroups);
  if (groups == nullptr || groups->elems.empty()) return;

  // PARQUET-2078: only the first row group's file_offset is trustworthy;
  // if the first chunk carries metadata, use page offsets instead.
  Value const& first_chunk0 = [&]() -> Value const& {
    Value const* cols = groups->elems[0].field(fid::kRgColumns);
    if (cols == nullptr || cols->elems.empty()) {
      throw std::invalid_argument("row group has no columns");
    }
    return cols->elems[0];
  }();
  bool use_chunk_meta = first_chunk0.field(fid::kCcMetaData) != nullptr;

  int64_t prev_start = 0;
  int64_t prev_compressed = 0;
  std::vector<Value> kept;
  std::vector<int32_t> kept_index;
  for (size_t g = 0; g < groups->elems.size(); ++g) {
    Value& rg = groups->elems[g];
    int64_t start;
    if (use_chunk_meta) {
      Value const* cols = rg.field(fid::kRgColumns);
      if (cols == nullptr || cols->elems.empty()) {
        throw std::invalid_argument("row group has no columns");
      }
      start = chunk_start_offset(cols->elems[0]);
    } else {
      Value const* fo = rg.field(fid::kRgFileOffset);
      start = fo ? fo->i : 0;
      bool invalid = prev_start == 0
                         ? start != 4
                         : start < prev_start + prev_compressed;
      if (invalid) {
        // first group always starts at 4 (after the PAR1 magic); later
        // groups fall back to the previous end (imprecise under padding
        // but fine for midpoint filtering)
        start = prev_start == 0 ? 4 : prev_start + prev_compressed;
      }
      prev_start = start;
      Value const* tcs = rg.field(fid::kRgTotalCompressedSize);
      prev_compressed = tcs ? tcs->i : 0;
    }

    int64_t total_size = 0;
    if (Value const* tcs = rg.field(fid::kRgTotalCompressedSize)) {
      total_size = tcs->i;
    } else if (Value const* cols = rg.field(fid::kRgColumns)) {
      for (Value const& cc : cols->elems) {
        if (Value const* md = cc.field(fid::kCcMetaData)) {
          if (Value const* sz = md->field(fid::kCmTotalCompressedSize)) {
            total_size += sz->i;
          }
        }
      }
    }

    int64_t mid_point = start + total_size / 2;
    if (mid_point >= part_offset && mid_point < part_offset + part_length) {
      kept.push_back(std::move(rg));
      kept_index.push_back(kept_groups_[g]);
    }
  }
  groups->elems = std::move(kept);
  kept_groups_ = std::move(kept_index);
}

std::vector<LeafInfo> Footer::leaves() const {
  std::vector<LeafInfo> out;
  Value const* schema = meta_.field(fid::kSchema);
  if (schema == nullptr) return out;
  for (size_t idx = 1; idx < schema->elems.size(); ++idx) {
    Value const& se = schema->elems[idx];
    Value const* type = se.field(fid::kSeType);
    if (type == nullptr) continue;  // a group, not a leaf
    LeafInfo leaf;
    leaf.physical = static_cast<int32_t>(type->i);
    if (Value const* f = se.field(fid::kSeConvertedType)) {
      leaf.converted = static_cast<int32_t>(f->i);
    }
    if (Value const* f = se.field(fid::kSeScale)) {
      leaf.scale = static_cast<int32_t>(f->i);
    }
    if (Value const* f = se.field(fid::kSeTypeLength)) {
      leaf.type_length = static_cast<int32_t>(f->i);
    }
    if (Value const* f = se.field(fid::kSeRepetition)) {
      leaf.repetition = static_cast<int32_t>(f->i);
    }
    out.push_back(std::move(leaf));
  }
  return out;
}

std::vector<int64_t> Footer::row_group_rows() const {
  std::vector<int64_t> out;
  if (Value const* groups = meta_.field(fid::kRowGroups)) {
    for (Value const& rg : groups->elems) {
      Value const* n = rg.field(fid::kRgNumRows);
      out.push_back(n ? n->i : 0);
    }
  }
  return out;
}

int64_t Footer::compressed_bytes() const {
  int64_t total = 0;
  Value const* groups = meta_.field(fid::kRowGroups);
  if (groups == nullptr) return total;
  for (Value const& rg : groups->elems) {
    Value const* cols = rg.field(fid::kRgColumns);
    if (cols == nullptr) continue;
    for (Value const& cc : cols->elems) {
      if (Value const* md = cc.field(fid::kCcMetaData)) {
        if (Value const* f = md->field(fid::kCmTotalCompressedSize)) {
          total += f->i;
        }
      }
    }
  }
  return total;
}

int64_t Footer::num_rows() const {
  Value const* groups = meta_.field(fid::kRowGroups);
  if (groups == nullptr) return 0;
  int64_t total = 0;
  for (Value const& rg : groups->elems) {
    if (Value const* n = rg.field(fid::kRgNumRows)) total += n->i;
  }
  return total;
}

int32_t Footer::num_columns() const {
  Value const* schema = meta_.field(fid::kSchema);
  if (schema == nullptr || schema->elems.empty()) return 0;
  Value const* nc = schema->elems[0].field(fid::kSeNumChildren);
  return nc ? static_cast<int32_t>(nc->i) : 0;
}

std::string Footer::serialize_framed() const {
  std::string body = thrift::serialize_struct(meta_);
  std::string out;
  out.reserve(body.size() + 12);
  out.append("PAR1");
  out.append(body);
  uint32_t n = static_cast<uint32_t>(body.size());
  for (int k = 0; k < 4; ++k) {
    out.push_back(static_cast<char>((n >> (8 * k)) & 0xFF));
  }
  out.append("PAR1");
  return out;
}

}  // namespace parquet
}  // namespace tpudf
